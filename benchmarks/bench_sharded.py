"""Shard-local compression vs gather-then-compress (DESIGN.md §6).

Two end-to-end checkpoint strategies over the SAME sharded train-state
pytree on an 8-device emulated ('data', 'model') mesh:

* gather-then-compress — the pre-§6 pipeline: `CheckpointManager` with
  `sharded=False` gathers every tensor to host (np.asarray inside
  `_leaf_items`), runs the batched selection engine on the gathered
  copies, and encodes whole fields;
* shard-local — `sharded=True`: decisions from per-shard statistics
  reconciled in-graph (no gather), per-shard segment encoding.

Standalone (needs the device-count flag BEFORE jax initializes, which the
module header sets):

    PYTHONPATH=src python -m benchmarks.bench_sharded [--fields 8] [--dim 1024]

The first sharded save compiles the engine's shard_map program (reported
separately as warmup); steady-state numbers are what an in-situ training
loop pays every checkpoint. Decision/value parity is asserted, not
assumed.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import argparse
import json
import tempfile
import time

import numpy as np


def run(n_fields: int = 8, dim: int = 1024, repeat: int = 3, eb_rel: float = 1e-3):
    import jax

    from repro.checkpoint import CheckpointConfig, CheckpointManager
    from repro.core import Policy
    from repro.launch.mesh import make_emulated_mesh
    from repro.launch.shardckpt import synth_state

    from .common import csv_row

    mesh = make_emulated_mesh((2, 4), ("data", "model"))
    tree, _ = synth_state(mesh, n_fields, dim)
    raw_mb = sum(x.size * np.dtype(str(x.dtype)).itemsize for x in jax.tree_util.tree_leaves(tree)) / 1e6
    rows = [csv_row("strategy", "fields", "dim", "devices", "warmup_s",
                    "save_s_median", "MB", "ratio", "speedup_vs_gather")]
    times = {}
    sizes = {}
    bits = {}
    for strategy, sharded in (("gather_then_compress", False), ("shard_local", True)):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(
                CheckpointConfig(
                    directory=d, policy=Policy.fixed_accuracy(eb_rel=eb_rel),
                    sharded=sharded, keep_n=1,
                )
            )
            t0 = time.perf_counter()
            mgr.save(0, tree)  # compiles (shard_map program / jit cache)
            warm = time.perf_counter() - t0
            ts = []
            for it in range(repeat):
                t0 = time.perf_counter()
                path = mgr.save(1 + it, tree)
                ts.append(time.perf_counter() - t0)
            with open(os.path.join(path, "manifest.json")) as f:
                man = json.load(f)
            _, restored = mgr.restore()
            # min, not median: the ratio below divides two of these, and
            # scheduler noise on small hosts only ever ADDS time — the
            # fastest repeat is the least-contended estimate of each side
            times[strategy] = (warm, float(np.min(ts)))
            sizes[strategy] = man["total_bytes"]
            bits[strategy] = man["selection_bits"]
            vals = restored
        if strategy == "gather_then_compress":
            ref_vals = vals
        else:
            flips = [k for k in bits["gather_then_compress"]
                     if bits["gather_then_compress"][k] != bits["shard_local"].get(k)]
            mism = [k for k in ref_vals if not np.array_equal(ref_vals[k], vals[k])]
            assert not flips, f"decision flips vs unsharded: {flips[:4]}"
            assert not mism, f"restored-value mismatches vs unsharded: {mism[:4]}"
    base = times["gather_then_compress"][1]
    for strategy in ("gather_then_compress", "shard_local"):
        warm, med = times[strategy]
        rows.append(csv_row(
            strategy, n_fields, dim, 8, f"{warm:.2f}", f"{med:.2f}",
            f"{sizes[strategy] / 1e6:.2f}",
            f"{raw_mb * 1e6 / max(sizes[strategy], 1):.2f}",
            f"{base / med:.2f}",
        ))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fields", type=int, default=8)
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--repeat", type=int, default=3)
    args = ap.parse_args()
    for row in run(args.fields, args.dim, args.repeat):
        print(row)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
