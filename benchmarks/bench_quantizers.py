"""Paper §5.1.4: rate-distortion comparison of the three vector-quantization
families (linear / log-scale / equal-probability) on the Stage-I residuals.

The paper argues: log-scale reaches higher PSNR per bin count but worse
entropy; equal-probability defeats entropy coding entirely (rate = log2 n);
'the most effective way is to compare their rate-distortion estimations' —
this benchmark does exactly that on each suite."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import quantize as q
from repro.core.transforms import lorenzo_forward
from repro.core.entropy import entropy_bits
from .common import SUITES, csv_row


def _rd_linear(r, vr, n_half):
    mx = np.abs(r).max() + 1e-12
    delta = 2 * mx / (2 * n_half - 1)
    k = np.round(r / delta)
    rec = k * delta
    return _pack(r, rec, k, vr)


def _rd_log(r, vr, n_half):
    codes, b = q.log_quantize(jnp.asarray(r), n_half, float(np.abs(r).max() + 1e-9))
    rec = np.asarray(q.log_dequantize(codes, b))
    return _pack(r, rec, np.asarray(codes), vr)


def _rd_equiprob(r, vr, n_bins):
    edges = np.asarray(q.equiprob_edges(jnp.asarray(r), n_bins))
    codes = np.asarray(q.equiprob_quantize(jnp.asarray(r), jnp.asarray(edges)))
    rec = np.asarray(q.equiprob_dequantize(jnp.asarray(codes), jnp.asarray(edges)))
    return _pack(r, rec, codes, vr)


def _pack(r, rec, codes, vr):
    mse = float(np.mean((r - rec) ** 2))
    psnr = -10 * np.log10(max(mse, 1e-30) / vr**2)
    hist = np.bincount((codes - codes.min()).astype(np.int64).reshape(-1))
    return entropy_bits(hist), psnr


def run(n_half: int = 256, suites=("ATM",)):
    rows = [csv_row("suite", "quantizer", "bits_per_value", "psnr_db", "psnr_per_bit")]
    for suite_name in suites:
        fields = dict(list(SUITES[suite_name]().items())[:6])
        agg = {"linear": [], "log": [], "equiprob": []}
        for f in fields.values():
            vr = float(f.max() - f.min())
            r = np.asarray(lorenzo_forward(jnp.asarray(f))).reshape(-1)
            agg["linear"].append(_rd_linear(r, vr, n_half))
            agg["log"].append(_rd_log(r, vr, n_half))
            agg["equiprob"].append(_rd_equiprob(r, vr, 2 * n_half - 1))
        for name, vals in agg.items():
            br = float(np.mean([v[0] for v in vals]))
            ps = float(np.mean([v[1] for v in vals]))
            rows.append(csv_row(suite_name, name, f"{br:.2f}", f"{ps:.1f}", f"{ps / max(br, 1e-9):.1f}"))
    return rows


def main() -> None:
    for r in run():
        print(r)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
