"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick]

Prints one CSV block per benchmark, prefixed with `== <name> ==`, plus a
`name,us_per_call,derived` summary line per benchmark (harness timing).
A benchmark that raises is printed as an `ERROR` row and the run exits
nonzero once every benchmark has had its turn.
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer suites/rates")
    args = ap.parse_args()

    from . import (
        bench_estimation,
        bench_grad_compress,
        bench_kernels3d,
        bench_overhead,
        bench_quantizers,
        bench_roofline,
        bench_selection,
        bench_throughput,
    )

    benches = [
        ("estimation_accuracy_T2_T5",
         (lambda: bench_estimation.run(rates=(0.05,), suites=("ATM",))) if args.quick
         else bench_estimation.run),
        ("selection_accuracy_F6_F7",
         (lambda: bench_selection.run(eb_rels=(1e-3,), suites=("ATM",))) if args.quick
         else bench_selection.run),
        ("overhead_T6",
         (lambda: bench_overhead.run(rates=(0.05,), suites=("ATM",))) if args.quick
         else bench_overhead.run),
        ("throughput_F8_F9", bench_throughput.run),
        ("quantizer_families_S514", bench_quantizers.run),
        ("grad_compress_beyond_paper",
         (lambda: bench_grad_compress.run(steps=10)) if args.quick
         else bench_grad_compress.run),
        ("kernels3d_vs_fallback",
         (lambda: bench_kernels3d.run(sizes=(128,), repeat=1)) if args.quick
         else bench_kernels3d.run),
        ("roofline_from_dryrun", bench_roofline.run),
    ]
    summary, failed = [], []
    for name, fn in benches:
        print(f"== {name} ==", flush=True)
        t0 = time.perf_counter()
        try:
            rows = fn()
            for r in rows:
                print(r)
            derived = len(rows) - 1
        except Exception as e:  # noqa: BLE001
            print(f"ERROR,{type(e).__name__},{e}")
            failed.append(name)
            derived = -1
        dt = (time.perf_counter() - t0) * 1e6
        summary.append(f"{name},{dt:.0f},{derived}")
        print(flush=True)
    print("== summary (name,us_per_call,derived) ==")
    for s in summary:
        print(s)
    if failed:
        raise SystemExit(f"{len(failed)} benchmark(s) raised: {', '.join(failed)}")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
