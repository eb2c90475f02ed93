"""Device-resident encode benchmark + parity probe (DESIGN.md §3.7).

Two questions about the in-graph Stage III (`core/device_encode.py`):

* **Parity** — fed the SAME quantized codes, do the device packer and the
  host Stage III emit BYTE-IDENTICAL container streams? This is the
  contract that lets the unchanged host decoders consume device-packed
  fields, and it feeds the bench gate's absolute `device_encode_parity`
  check: the mismatch list must be empty, and a run where the device tier
  declined every field (all-fallback) counts as vacuous and fails.

* **Speedup** — end-to-end encode (field in device memory -> container
  bytes on host) with the device tier vs. the host coder, on 3-D
  NYX-like smoke fields. The host path ships raw f32 values across the
  interconnect and runs the f64 coder loops; the device path ships one
  packed word arena plus small sidecars. Reported as
  `device_encode_speedup`: the geometric mean across every measured
  (field, codec) row — the save-path aggregate over the bench suite —
  gated by the 20% regression rule, with the per-codec geomeans
  alongside in `speedups`. The per-codec picture on the CPU bench host
  is asymmetric by design: SZ's gather-packed Huffman wins at every
  size, while ZFP's chunk emitter pays XLA:CPU's serialized scatter and
  only crosses over at 256^3 (the host coder's plane loops scale
  superlinearly); on an accelerator both tiers also avoid shipping the
  raw field.

    PYTHONPATH=src python -m benchmarks.bench_device_encode     # 128^3/256^3
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import csv_row, nyx_suite


def _encode_host(x, eb, codec):
    from repro.core import sz_compress, zfp_compress

    return sz_compress(x, eb) if codec == "sz" else zfp_compress(x, eb)


def _encode_device(x, eb, codec):
    from repro.core import device_encode as de

    if codec == "sz":
        return de.sz_encode_device(x, eb)
    return de.zfp_encode_device(x, eb)


def _parity_check(name, x, eb) -> list[str]:
    """Byte-compare device streams against the host Stage III over the
    device's own codes (quantization held fixed, so any diff is the
    packer's fault)."""
    from repro.core import device_encode as de, sz, zfp

    bad = []
    dev_sz = de.sz_encode_device(x, eb)
    if dev_sz is not None:
        d = de.sz_device_residuals(x, eb)
        delta = float(np.float32(2.0) * np.float32(eb))
        host = sz.sz_encode_residuals(d, x.shape, delta, magic=sz.DEVICE_MAGIC)
        if dev_sz != host:
            bad.append(f"{name}:sz")
    else:
        bad.append(f"{name}:sz (declined)")
    dev_zfp = de.zfp_encode_device(x, eb)
    if dev_zfp is not None:
        q, e = de.zfp_device_codes(x, eb)
        padded = tuple(s + (-s) % 4 for s in x.shape)
        if dev_zfp != zfp.zfp_encode_quantized(q, e, x.shape, padded, eb):
            bad.append(f"{name}:zfp")
    else:
        bad.append(f"{name}:zfp (declined)")
    return bad


def _time_encode(x, eb, codec, fn, repeat) -> float:
    import jax

    xd = jax.device_put(np.asarray(x, np.float32))
    fn(xd, eb, codec)  # warm the jit caches / BLAS outside the clock
    t0 = time.perf_counter()
    for _ in range(repeat):
        buf = fn(xd, eb, codec)
        assert buf is not None and len(buf) > 0
    return (time.perf_counter() - t0) / repeat


def _geomean(vals) -> float:
    return float(np.exp(np.mean(np.log(vals)))) if len(vals) else 0.0


def run(size: int = 64, n_fields: int = 2, repeat: int = 3,
        eb_rel: float = 1e-3) -> dict:
    """Gate entry point: parity over the smoke fields + the end-to-end
    speedup aggregate (geomean over (field, codec) rows). Returns
    {speedups, device_encode_speedup, parity_mismatches, fields, rows}."""
    fields = nyx_suite(n_fields, size=(size, size, size))
    mismatches: list[str] = []
    rows = [csv_row("field", "codec", "host_s", "device_s", "speedup",
                    "device_bytes")]
    per_codec: dict[str, list[float]] = {"sz": [], "zfp": []}
    for name, x in fields.items():
        eb = eb_rel * float(x.max() - x.min())
        mismatches += _parity_check(name, x, eb)
        for codec in ("sz", "zfp"):
            th = _time_encode(x, eb, codec, _encode_host, repeat)
            td = _time_encode(x, eb, codec, _encode_device, repeat)
            nb = len(_encode_device(np.asarray(x, np.float32), eb, codec))
            per_codec[codec].append(th / td)
            rows.append(csv_row(name, codec, f"{th:.4f}", f"{td:.4f}",
                                f"{th / td:.2f}", nb))
    speedups = {codec: _geomean(vals) for codec, vals in per_codec.items()}
    return {
        "speedups": speedups,
        "device_encode_speedup": _geomean(
            [r for vals in per_codec.values() for r in vals]
        ),
        "parity_mismatches": mismatches,
        "fields": len(fields),
        "rows": rows,
    }


def main():
    # full measurement at the acceptance sizes (128^3 and 256^3)
    all_ratios: list[float] = []
    mismatches: list[str] = []
    for size, n in ((128, 2), (256, 1)):
        out = run(size=size, n_fields=n, repeat=3)
        print(f"--- {size}^3 ---")
        for r in out["rows"]:
            print(r)
        print(f"per-codec geomean: {out['speedups']}; "
              f"parity mismatches: {out['parity_mismatches'] or 'none'}")
        all_ratios += [float(r.split(",")[4]) for r in out["rows"][1:]]
        mismatches += out["parity_mismatches"]
    print(f"overall save-path speedup (geomean, all rows): "
          f"{_geomean(all_ratios):.2f}x; "
          f"parity mismatches: {mismatches or 'none'}")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
