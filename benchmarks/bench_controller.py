"""Quality-target controller (DESIGN.md §7): achieved-vs-target accuracy
and controller overhead on the paper-style suites.

For each suite x target, every field is solved (`solve_many`, batched
sweep launches only — no trial compressions) and then actually encoded;
the report compares the achieved PSNR / compression ratio of the real
byte streams against the target, and the controller's solve time against
the time spent encoding (the acceptance bar is solve < 10% of compress).

  PYTHONPATH=src python -m benchmarks.bench_controller
  PYTHONPATH=src python -m benchmarks.bench_controller --psnr=50,70 --ratio=4,8,16
"""

from __future__ import annotations

import numpy as np

from repro.core import Policy, decompress, encode_with_selection, solve_many
from .common import SUITES, csv_row, psnr as _psnr, timer


def _run_mode(fields, mode, target):
    pol = Policy.fixed_psnr(target) if mode == "fixed_psnr" else Policy.fixed_ratio(target)
    arrs = list(fields.values())
    solve_many(arrs, pol)  # warm the sweep jit cache before timing
    sols, t_solve = timer(solve_many, arrs, pol)
    encs, t_encode = timer(
        lambda: [encode_with_selection(a, s.selection) for a, s in zip(arrs, sols)]
    )
    errs, ratios, codecs = [], [], {"sz": 0, "zfp": 0, "raw": 0}
    for a, cf in zip(arrs, encs):
        rec = decompress(cf).reshape(a.shape)
        ratios.append(a.size * 4 / len(cf.data))
        errs.append(_psnr(a, rec))
        codecs[cf.codec] += 1
    return sols, np.asarray(errs), np.asarray(ratios), codecs, t_solve, t_encode


def run(psnr_targets=(50.0, 70.0), ratio_targets=(4.0, 8.0, 16.0), suites=("ATM", "Hurricane", "NYX")):
    rows = [csv_row("suite", "mode", "target", "n", "achieved_p50", "achieved_worst",
                    "miss_p50", "miss_worst", "picks(sz/zfp/raw)",
                    "solve_s", "encode_s", "overhead_pct")]
    for suite_name in suites:
        fields = SUITES[suite_name]()
        for target in psnr_targets:
            sols, psnrs, _, codecs, t_s, t_e = _run_mode(fields, "fixed_psnr", target)
            miss = np.abs(psnrs - target)
            lossy = np.asarray([s.selection.codec != "raw" for s in sols])
            m = miss[lossy] if lossy.any() else miss
            p = psnrs[lossy] if lossy.any() else psnrs
            rows.append(csv_row(
                suite_name, "fixed_psnr", f"{target:g}dB", len(fields),
                f"{np.median(p):.2f}dB", f"{p[np.argmax(m)]:.2f}dB",
                f"{np.median(m):.2f}dB", f"{m.max():.2f}dB",
                f"{codecs['sz']}/{codecs['zfp']}/{codecs['raw']}",
                f"{t_s:.3f}", f"{t_e:.3f}", f"{100 * t_s / max(t_e, 1e-9):.1f}",
            ))
        for target in ratio_targets:
            sols, _, ratios, codecs, t_s, t_e = _run_mode(fields, "fixed_ratio", target)
            on = np.asarray([s.on_target for s in sols])
            r = ratios[on] if on.any() else ratios
            miss = np.abs(r / target - 1.0) * 100
            rows.append(csv_row(
                suite_name, "fixed_ratio", f"{target:g}x", len(fields),
                f"{np.median(r):.2f}x", f"{r[np.argmax(miss)]:.2f}x",
                f"{np.median(miss):.1f}%", f"{miss.max():.1f}%",
                f"{codecs['sz']}/{codecs['zfp']}/{codecs['raw']}",
                f"{t_s:.3f}", f"{t_e:.3f}", f"{100 * t_s / max(t_e, 1e-9):.1f}",
            ))
    return rows


def main(argv: list[str] | None = None) -> None:
    import sys

    argv = sys.argv[1:] if argv is None else argv
    kw = {}
    for a in argv:
        if a.startswith("--psnr="):
            kw["psnr_targets"] = tuple(float(x) for x in a.split("=", 1)[1].split(","))
        elif a.startswith("--ratio="):
            kw["ratio_targets"] = tuple(float(x) for x in a.split("=", 1)[1].split(","))
    for r in run(**kw):
        print(r)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
