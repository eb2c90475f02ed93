"""Paper Figs 8-9: storing/loading throughput vs process count.

Real-time codec rates are measured on this machine; the parallel file
system is modeled as a saturating shared-bandwidth resource
(B_eff(p) = B_max * p / (p + p_half), GPFS-like contention curve, per [56]).
Store time per process = compress + write(bytes/B_eff); load = read +
decompress. Throughput = p * field_bytes / time — the paper's setup with
file-per-process POSIX I/O."""

from __future__ import annotations


from repro.core import (
    select_and_compress, decompress, sz_compress, sz_decompress,
    zfp_compress, zfp_decompress,
)
from .common import SUITES, csv_row, timer

B_MAX = 90e9      # aggregate PFS bandwidth (GPFS-class), B/s
P_HALF = 64       # half-saturation process count
PER_PROC = 1.2e9  # single-stream cap, B/s


def _b_eff(p: int) -> float:
    agg = B_MAX * p / (p + P_HALF)
    return min(agg, p * PER_PROC)


def run(eb_rel: float = 1e-4, procs=(1, 16, 64, 256, 1024), suite="Hurricane"):
    fields = dict(list(SUITES[suite]().items())[:4])
    raw = sum(f.nbytes for f in fields.values())
    # measured codec rates (B/s) and sizes
    meas = {}
    for codec in ("baseline", "sz", "zfp", "ours"):
        csize = 0
        t_c = t_d = 1e-12
        for f in fields.values():
            eb = eb_rel * float(f.max() - f.min())
            if codec == "baseline":
                blob, dt = f.tobytes(), 1e-9
                csize += len(blob)
                t_c += dt
                t_d += 1e-9
            elif codec == "sz":
                blob, dt = timer(sz_compress, f, eb)
                csize += len(blob)
                t_c += dt
                _, dt = timer(sz_decompress, blob)
                t_d += dt
            elif codec == "zfp":
                blob, dt = timer(zfp_compress, f, eb)
                csize += len(blob)
                t_c += dt
                _, dt = timer(zfp_decompress, blob)
                t_d += dt
            else:
                cf, dt = timer(select_and_compress, f, eb_abs=eb)
                csize += len(cf.data)
                t_c += dt
                _, dt = timer(decompress, cf)
                t_d += dt
        meas[codec] = dict(
            ratio=raw / csize,
            c_rate=raw / t_c,   # compression throughput, B/s/proc
            d_rate=raw / t_d,
        )
    rows = [csv_row("codec", "procs", "ratio", "store_GBps", "load_GBps")]
    field_bytes = raw / len(fields)
    for codec, m in meas.items():
        for p in procs:
            io_bw = _b_eff(p)
            comp_bytes = field_bytes / m["ratio"]
            t_store = field_bytes / m["c_rate"] + comp_bytes * p / io_bw
            t_load = field_bytes / m["d_rate"] + comp_bytes * p / io_bw
            rows.append(csv_row(
                codec, p, f"{m['ratio']:.2f}",
                f"{p * field_bytes / t_store / 1e9:.2f}",
                f"{p * field_bytes / t_load / 1e9:.2f}",
            ))
    return rows


def main() -> None:
    for r in run():
        print(r)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
