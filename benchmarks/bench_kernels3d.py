"""ISSUE 4 acceptance bench: the 3-D Pallas kernel tier vs the pre-PR
fallback path (DESIGN.md §3.4–§3.5).

encode = fused prequantize + 3-D integer-Lorenzo (SZ Stage I+II); stats =
fused 4x4x4 BOT + truncate + closed-form rate (ZFP Stage I+II). The
fallback is what `kernels/ops.py` dispatched 3-D shapes to before the
kernel tier existed: the jnp `lorenzo_forward(round(x/2eb))` reference
and `core.zfp.zfp_stats` (whose exact coder runs the 31-plane loop).

  PYTHONPATH=src python -m benchmarks.bench_kernels3d [--sizes 256,512]

Default sizes are CPU-friendly (128^3, 256^3 ~ the NYX cube of
`launch.shapes.FIELD_SHAPES`); pass --sizes 512 for the paper-scale cube
on real hardware. The `speedup` column (old encode+stats time over new)
is the ratio the CI bench gate tracks (tools/bench_gate.py).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from .common import csv_row


def _timer(fn, *args, repeat: int = 3):
    """Min-of-repeats wall time (the standard microbench statistic — the
    min is the least load-contaminated sample, which matters because the
    CI bench gate compares these as ratios against a committed baseline)."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # warm-up: compile outside the timed region
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _paths():
    import jax
    import jax.numpy as jnp

    from repro.core.transforms import lorenzo_forward
    from repro.core.zfp import zfp_stats
    from repro.kernels import ops

    old_enc = jax.jit(
        lambda x, eb: lorenzo_forward(jnp.round(x / (2.0 * eb))).astype(jnp.int32)
    )

    def _old_stats(x, eb):
        st = zfp_stats(x, eb)
        return st.recon, st.bitrate

    return {
        "new_encode": lambda x, eb: ops.lorenzo_encode(x, eb),
        "new_stats": lambda x, eb: ops.bot_fused(x, eb),
        "old_encode": old_enc,
        "old_stats": jax.jit(_old_stats),
    }


def run(sizes=None, repeat: int = 3, seed: int = 0):
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.launch.shapes import FIELD_SHAPES

    if sizes is None:
        # the catalog's CPU-scaled NYX cube edge and the Hurricane-like
        # trailing edge (launch.shapes.FIELD_SHAPES) -> 128^3 and 256^3
        sizes = (FIELD_SHAPES["nyx_3d"][0], FIELD_SHAPES["hurricane_3d"][-1])

    p = _paths()
    rows = [
        csv_row(
            "shape", "enc_new_ms", "enc_old_ms", "stats_new_ms", "stats_old_ms",
            "speedup_encode_stats",
        )
    ]
    for n in sizes:
        shape = (n, n, n)
        assert ops.pallas_rank(shape) == 3, "bench field must ride the 3-D tier"
        rng = np.random.default_rng(seed)
        x = jnp.asarray(
            np.cumsum(rng.standard_normal(shape), axis=-1).astype(np.float32)
        )
        eb = jnp.float32(1e-3 * float(jnp.max(x) - jnp.min(x)))
        te_new = _timer(p["new_encode"], x, eb, repeat=repeat)
        ts_new = _timer(p["new_stats"], x, eb, repeat=repeat)
        te_old = _timer(p["old_encode"], x, eb, repeat=repeat)
        # the 31-plane exact coder is 10-50x the kernel path; at bench
        # scale once is plenty, at gate scale keep the min-of-repeats
        ts_old = _timer(p["old_stats"], x, eb, repeat=repeat if n <= 128 else 1)
        speedup = (te_old + ts_old) / (te_new + ts_new)
        rows.append(
            csv_row(
                f"{n}^3",
                f"{te_new * 1e3:.1f}", f"{te_old * 1e3:.1f}",
                f"{ts_new * 1e3:.1f}", f"{ts_old * 1e3:.1f}",
                f"{speedup:.2f}",
            )
        )
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--sizes", default=None,
        help="comma list of cube edges (default: from launch.shapes.FIELD_SHAPES)",
    )
    ap.add_argument("--repeat", type=int, default=3)
    args = ap.parse_args()
    sizes = tuple(int(s) for s in args.sizes.split(",")) if args.sizes else None
    for r in run(sizes=sizes, repeat=args.repeat):
        print(r)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
