"""Sharded-checkpoint dryrun scenario (DESIGN.md §6).

Emulates the paper's parallel setting on CPU: builds an 8-device
('data', 'model') mesh, synthesizes a train-state-like pytree of sharded
fields (FSDP-style weight sharding + replicated small tensors + raw
optimizer state), then exercises the full shard-local pipeline:

  1. `CheckpointManager(sharded=True).save` — decisions from per-shard
     statistics (no gather), per-shard segment encoding, segment manifest;
  2. elastic restore under a DIFFERENT mesh shape via
     `restore_tree(shardings=...)`;
  3. a parity check against the unsharded writer.

Run it to sanity-check a jax upgrade or a new mesh layout end to end:

    PYTHONPATH=src python -m repro.launch.shardckpt [--fields 12] [--dim 512]

`--processes N` (N in {2, 4}) runs the MULTI-HOST dryrun instead
(DESIGN.md §6.2): N worker processes join one distributed CPU job via
`launch/mhrun.py` (8 global emulated devices split across them), save
one sharded checkpoint cooperatively — per-host `data.<host>.bin` +
completion markers, host-0 manifest assembly — then elastically restore
it with per-host segment locality, and the driver prints each host's
byte counts and locality stats:

    PYTHONPATH=src python -m repro.launch.shardckpt --processes 2
"""

import os
import sys

if __name__ == "__main__" and "--mh-worker" not in sys.argv:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()

import argparse
import json
import tempfile
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.core import Policy
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import describe_mesh, make_emulated_mesh


def synth_state(mesh, n_fields: int, dim: int, seed: int = 0):
    """A train-state-like pytree: weights sharded FSDP-style over 'data' /
    TP-style over 'model', a replicated norm table, raw optimizer moments.
    Placement rides `dist.put_global`, so the same synthesis works when
    `mesh` spans processes (every worker seeds identically and contributes
    only its addressable shards) — the multi-host dryrun and test workers
    build their state with exactly this function."""
    from repro.runtime import dist

    rng = np.random.default_rng(seed)
    tree: dict = {"params": {}, "opt": {}}
    shardings: dict = {"params": {}, "opt": {}}
    for i in range(n_fields):
        name = f"layer{i:02d}/w"
        x = np.cumsum(rng.standard_normal((dim, dim)), axis=0).astype(np.float32)
        spec = P("data", None) if i % 2 == 0 else P(None, "model")
        tree["params"][name] = dist.put_global(x, NamedSharding(mesh, spec))
        shardings["params"][name] = NamedSharding(mesh, spec)
        m = (0.01 * rng.standard_normal((dim, dim))).astype(np.float32)
        tree["opt"][name] = dist.put_global(m, NamedSharding(mesh, spec))
        shardings["opt"][name] = NamedSharding(mesh, spec)
    norm = np.linspace(0.9, 1.1, dim, dtype=np.float32)
    tree["params"]["norm"] = dist.put_global(norm, NamedSharding(mesh, P()))
    shardings["params"]["norm"] = NamedSharding(mesh, P())
    # int32: jax without x64 canonicalizes wider ints on placement, which
    # would make the restored-through-device value differ from the saved one
    tree["step"] = np.array(1234, np.int32)
    shardings["step"] = NamedSharding(mesh, P())
    return tree, shardings


def _mh_dryrun(spec: dict, pid: int) -> dict:
    """Worker body for `--processes N`: cooperative sharded save + local
    elastic restore on the shared 8-device (2, 4) mesh."""
    a = spec["args"]
    mesh = make_emulated_mesh((2, jax.device_count() // 2), ("data", "model"))
    tree, shardings = synth_state(mesh, int(a["fields"]), int(a["dim"]))
    mgr = CheckpointManager(
        CheckpointConfig(
            directory=a["directory"],
            policy=Policy.fixed_accuracy(eb_rel=float(a["eb_rel"])),
            sharded=True,
            barrier_timeout_s=60.0,
        )
    )
    t0 = time.perf_counter()
    path = mgr.save(1, tree)
    t_save = time.perf_counter() - t0
    own_bytes = os.path.getsize(os.path.join(path, f"data.{pid}.bin"))
    t0 = time.perf_counter()
    _, restored = mgr.restore_tree(tree, shardings=shardings)
    t_restore = time.perf_counter() - t0
    w0 = np.asarray(
        jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))(
            restored["params"]["layer00/w"]
        )
    )
    exact = bool(
        np.allclose(
            w0,
            np.asarray(
                jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))(
                    tree["params"]["layer00/w"]
                )
            ),
            atol=float(a["eb_rel"]) * float(np.abs(w0).max() + 1.0),
        )
    )
    with open(os.path.join(path, "manifest.json")) as f:
        man = json.load(f)
    return dict(
        mesh=describe_mesh(mesh),
        path=path,
        save_seconds=t_save,
        restore_seconds=t_restore,
        own_bytes=int(own_bytes),
        total_bytes=int(man["total_bytes"]),
        restore_stats=mgr.last_restore_stats,
        within_bound=exact,
    )


def _run_multiprocess(args) -> None:
    from repro.launch import mhrun

    with tempfile.TemporaryDirectory() as wd:
        results = mhrun.run(
            [sys.executable, "-m", "repro.launch.shardckpt", "--mh-worker"],
            args.processes,
            scenario="dryrun",
            args=dict(
                fields=args.fields, dim=args.dim, eb_rel=args.eb_rel,
                directory=os.path.join(wd, "ckpt"),
            ),
            local_devices=8 // args.processes,
            timeout_s=600.0,
            workdir=os.path.join(wd, "mhrun"),
        )
        payloads = mhrun.require_success(results)
        for p in payloads:
            mesh = p["mesh"]
            print(
                f"host {mesh['process_index']}/{mesh['process_count']}: "
                f"wrote {p['own_bytes'] / 1e6:.2f} MB of "
                f"{p['total_bytes'] / 1e6:.2f} MB total; save {p['save_seconds']:.2f}s, "
                f"restore {p['restore_seconds']:.2f}s decoding "
                f"{p['restore_stats']['segments_decoded']}/"
                f"{p['restore_stats']['segments_total']} segments "
                f"from hosts {p['restore_stats']['hosts_opened']} "
                f"(within_bound={p['within_bound']})"
            )
        if not all(p["within_bound"] for p in payloads):
            raise SystemExit("MULTI-HOST DRYRUN FAILURE: restored values out of bound")
    print(f"multi-host dryrun OK ({args.processes} processes)")


def main() -> None:
    use_compile_cache()
    if "--mh-worker" in sys.argv:
        from repro.launch import mhrun

        raise SystemExit(mhrun.worker_main(sys.argv[-1], {"dryrun": _mh_dryrun}))
    ap = argparse.ArgumentParser()
    ap.add_argument("--fields", type=int, default=12)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--eb-rel", type=float, default=1e-3)
    ap.add_argument(
        "--processes", type=int, default=1, choices=(1, 2, 4),
        help="run the multi-host dryrun with N distributed worker processes",
    )
    args = ap.parse_args()
    if args.processes > 1:
        _run_multiprocess(args)
        return

    mesh = make_emulated_mesh((2, 4), ("data", "model"))
    with tempfile.TemporaryDirectory() as d:
        dryrun(mesh, make_emulated_mesh((4, 2), ("data", "model")),
               args.fields, args.dim, args.eb_rel, d)


def placement(x) -> dict[str, tuple[int, ...]]:
    """{device: local shard shape} of a jax.Array."""
    return {str(sh.device): tuple(sh.data.shape) for sh in x.addressable_shards}


def dryrun(mesh, mesh2, n_fields: int, dim: int, eb_rel: float, directory: str) -> dict:
    """Sharded save on `mesh`, elastic restore under `mesh2`, and decision
    + value parity against the unsharded writer; prints one line per step
    and raises SystemExit on any mismatch."""
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"({mesh.devices.size} {mesh.devices.flat[0].platform} devices)")
    tree, _ = synth_state(mesh, n_fields, dim)
    d_sh, d_un = os.path.join(directory, "sharded"), os.path.join(directory, "unsharded")
    w0 = "layer00/w"
    saved_on = placement(tree["params"][w0])
    print(f"save placement of params/{w0}: {saved_on}")
    msh = CheckpointManager(
        CheckpointConfig(
            directory=d_sh, policy=Policy.fixed_accuracy(eb_rel=eb_rel), sharded=True
        )
    )
    t0 = time.perf_counter()
    path = msh.save(1, tree)
    t_sh = time.perf_counter() - t0
    with open(os.path.join(path, "manifest.json")) as f:
        man = json.load(f)
    n_segs = sum(len(fl["segments"]) for fl in man["fields"])
    print(f"sharded save: {t_sh:.2f}s  {man['total_bytes']/1e6:.2f} MB "
          f"({man['raw_bytes']/max(man['total_bytes'],1):.2f}x) "
          f"{len(man['fields'])} fields / {n_segs} segments")

    host_tree = jax.tree_util.tree_map(np.asarray, tree)
    mun = CheckpointManager(
        CheckpointConfig(directory=d_un, policy=Policy.fixed_accuracy(eb_rel=eb_rel))
    )
    t0 = time.perf_counter()
    mun.save(1, host_tree)
    t_un = time.perf_counter() - t0
    print(f"gather-then-compress save: {t_un:.2f}s "
          f"(shard-local is {t_un / max(t_sh, 1e-9):.2f}x)")

    # elastic restore: consume the checkpoint under the second mesh
    _, shardings2 = synth_state(mesh2, n_fields, dim)
    t0 = time.perf_counter()
    _, restored = msh.restore_tree(tree, shardings=shardings2)
    t_rs = time.perf_counter() - t0
    ok_spec = restored["params"][w0].sharding.mesh.devices.shape == mesh2.devices.shape
    restored_on = placement(restored["params"][w0])
    print(f"elastic restore onto {dict(zip(mesh2.axis_names, mesh2.devices.shape))} "
          f"mesh: {t_rs:.2f}s resharded={ok_spec}; placement of params/{w0}: "
          f"{restored_on}")

    # decision + value parity against the unsharded writer
    _, f_sh = msh.restore()
    _, f_un = mun.restore()
    mism = [k for k in f_un if not np.array_equal(f_un[k], f_sh[k])]
    bits_sh = man["selection_bits"]
    with open(os.path.join(d_un, f"step_{1:09d}", "manifest.json")) as f:
        bits_un = json.load(f)["selection_bits"]
    flips = [k for k in bits_un if bits_un[k] != bits_sh.get(k)]
    print(f"parity: {len(mism)} value mismatches, {len(flips)} decision flips "
          f"across {len(f_un)} fields")
    if mism or flips or not ok_spec:
        raise SystemExit(f"PARITY FAILURE: {mism[:3]} {flips[:3]} resharded={ok_spec}")
    print("dryrun OK")
    return dict(
        save_s=t_sh, unsharded_save_s=t_un, restore_s=t_rs, fields=len(f_un),
        saved_on=saved_on, restored_on=restored_on,
    )


if __name__ == "__main__":
    main()
