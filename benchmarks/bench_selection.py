"""Paper §6.2 + Figs 6-7: selection accuracy, compression-ratio improvement
at iso-PSNR, and the fixed-eb (Lu et al.) vs fixed-PSNR comparison.

`run_many` / `--many`: the batched multi-field engine (`select_many`,
DESIGN.md §1) vs the per-field `select` loop on a many-tensor checkpoint —
one padded block batch + one jitted launch vs one launch (and up to one
compile) per field."""

from __future__ import annotations

import numpy as np

from repro.core import select, select_many, sz_compress, zfp_compress
from .common import SUITES, csv_row, timer


def run(eb_rels=(1e-3, 1e-4), suites=("ATM", "Hurricane", "NYX")):
    rows = [csv_row("suite", "eb_rel", "n_fields", "accuracy",
                    "cr_sz_only", "cr_zfp_only", "cr_ours", "cr_optimum",
                    "improve_vs_worst_pct", "degradation_pct", "fixed_eb_picks_sz_pct")]
    for suite_name in suites:
        fields = SUITES[suite_name]()
        for eb_rel in eb_rels:
            n_ok = 0
            bits = {"sz": 0.0, "zfp": 0.0, "ours": 0.0, "opt": 0.0}
            raw_bits = 0.0
            degr = []
            fixed_eb_sz = 0
            for name, f in fields.items():
                vr = float(f.max() - f.min())
                eb = eb_rel * vr
                sel = select(f, eb_abs=eb)
                # iso-PSNR actuals (SZ run at the matched bin size)
                b_sz = 8 * len(sz_compress(f, sel.eb_sz))
                b_zfp = 8 * len(zfp_compress(f, eb))
                best = "sz" if b_sz < b_zfp else "zfp"
                pick = sel.codec if sel.codec in ("sz", "zfp") else best
                n_ok += pick == best
                bits["sz"] += b_sz
                bits["zfp"] += b_zfp
                bits["ours"] += b_sz if pick == "sz" else b_zfp
                bits["opt"] += min(b_sz, b_zfp)
                if pick != best:
                    degr.append(max(b_sz, b_zfp) / min(b_sz, b_zfp) - 1)
                # Lu-et-al-style fixed-eb selection: larger CR at the SAME eb
                b_sz_fixed = 8 * len(sz_compress(f, eb))
                fixed_eb_sz += b_sz_fixed < b_zfp
                raw_bits += f.size * 32
            n = len(fields)
            crs = {k: raw_bits / v for k, v in bits.items()}
            worst = min(crs["sz"], crs["zfp"])
            rows.append(csv_row(
                suite_name, eb_rel, n, f"{n_ok / n:.3f}",
                f"{crs['sz']:.2f}", f"{crs['zfp']:.2f}", f"{crs['ours']:.2f}",
                f"{crs['opt']:.2f}",
                f"{100 * (crs['ours'] / worst - 1):.1f}",
                f"{100 * float(np.mean(degr)) if degr else 0:.2f}",
                f"{100 * fixed_eb_sz / n:.0f}",
            ))
    return rows


def _checkpoint_fields(n_fields: int, seed: int = 0) -> list[np.ndarray]:
    """A checkpoint-like mix: varied 1/2/3-D shapes and characteristics, so
    the per-field loop pays its worst case (jit cache misses across shapes)
    and the batched engine shows its amortization."""
    rng = np.random.default_rng(seed)
    shapes = [(256, 256), (192, 320), (128, 128), (4096,), (16, 64, 64), (96, 224)]
    out = []
    for i in range(n_fields):
        shape = shapes[i % len(shapes)]
        slope = -4.0 + 3.0 * (i % 7) / 6.0
        grids = np.meshgrid(*[np.linspace(0, 5, s) for s in shape], indexing="ij")
        smooth = np.ones(shape, np.float32)
        for g in grids:
            smooth = smooth * np.sin((1 + i % 5) * g).astype(np.float32)
        f = smooth + 10.0**slope * rng.standard_normal(shape).astype(np.float32)
        out.append(f.astype(np.float32))
    return out


def run_many(n_fields: int = 32, eb_rel: float = 1e-4, repeat: int = 3):
    """Batched `select_many` vs the per-field `select` loop."""
    fields = _checkpoint_fields(n_fields)
    # warm both paths (compile) before timing
    loop_sels = [select(f, eb_rel=eb_rel) for f in fields]
    many_sels = select_many(fields, eb_rel=eb_rel)
    agree = sum(a.codec == b.codec for a, b in zip(loop_sels, many_sels))
    t_loop = min(
        timer(lambda: [select(f, eb_rel=eb_rel) for f in fields])[1]
        for _ in range(repeat)
    )
    t_many = min(
        timer(select_many, fields, eb_rel=eb_rel)[1] for _ in range(repeat)
    )
    rows = [csv_row("n_fields", "t_per_field_s", "t_batched_s", "speedup", "decisions_agree")]
    rows.append(csv_row(
        n_fields, f"{t_loop:.4f}", f"{t_many:.4f}",
        f"{t_loop / max(t_many, 1e-9):.2f}", f"{agree}/{n_fields}",
    ))
    return rows


def main(argv: list[str] | None = None) -> None:
    import sys

    argv = sys.argv[1:] if argv is None else argv
    if "--many" in argv:
        n = 32
        for a in argv:
            if a.startswith("--fields="):
                n = int(a.split("=", 1)[1])
        for r in run_many(n_fields=n):
            print(r)
        return
    for r in run():
        print(r)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
