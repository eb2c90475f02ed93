"""Paper Tables 2-5: average + std of relative estimation error for bit-rate
and PSNR, per data-set suite, per sampling rate (1%, 5%, 10%)."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import select, sz_compress, sz_stats, zfp_compress, zfp_stats
from .common import SUITES, csv_row


def run(eb_rel: float = 1e-3, rates=(0.01, 0.05, 0.10), suites=("ATM", "Hurricane")):
    rows = [csv_row("suite", "r_sp", "metric", "codec", "avg_rel_err", "std_rel_err")]
    for suite_name in suites:
        fields = SUITES[suite_name]()
        for r_sp in rates:
            errs = {("br", "sz"): [], ("br", "zfp"): [], ("psnr", "sz"): [], ("psnr", "zfp"): []}
            for name, f in fields.items():
                vr = float(f.max() - f.min())
                eb = eb_rel * vr
                sel = select(f, eb_abs=eb, r_sp=r_sp)
                # actual rates from the byte codecs
                a_sz = 8 * len(sz_compress(f, sel.eb_sz)) / f.size
                a_zfp = 8 * len(zfp_compress(f, eb)) / f.size
                errs[("br", "sz")].append((sel.br_sz - a_sz) / a_sz)
                errs[("br", "zfp")].append((sel.br_zfp - a_zfp) / a_zfp)
                # actual PSNR from the stats paths (== codec reconstructions)
                p_sz = float(sz_stats(jnp.asarray(f), sel.eb_sz).psnr)
                p_zfp = float(zfp_stats(jnp.asarray(f), eb).psnr)
                est_p_sz = float(
                    __import__("repro.core.estimator", fromlist=["sz_psnr"]).sz_psnr(sel.eb_sz, vr)
                )
                errs[("psnr", "sz")].append((est_p_sz - p_sz) / p_sz)
                errs[("psnr", "zfp")].append((sel.psnr_target - p_zfp) / p_zfp)
            for (metric, codec), v in errs.items():
                v = np.asarray(v)
                rows.append(
                    csv_row(suite_name, r_sp, metric, codec,
                            f"{np.mean(v):+.4f}", f"{np.std(v):.4f}")
                )
    return rows


def main() -> None:
    for r in run():
        print(r)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
