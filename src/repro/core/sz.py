"""SZ-style prediction-based error-bounded lossy compressor (paper §2, §5.1).

Pipeline (Stage I/II/III of Fig. 1):
  PBT (integer Lorenzo, DESIGN.md §3.1)  ->  linear quantization (delta=2*eb)
  ->  Huffman entropy coding.

Two paths:
  * `sz_stats`      — jnp / jit-safe: reconstruction + exact rate/distortion
                      statistics (histogram entropy + the paper's +0.5 offset).
  * `sz_compress` / `sz_decompress` — host numpy byte codec (real Stage III).

The pointwise guarantee |x - x~| <= eb holds by construction (prequantization
+ Theorem 1: integer Lorenzo is lossless so the only error is quantization).
"""

from __future__ import annotations

import struct
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import entropy as _entropy
from .transforms import lorenzo_forward

#: symbols: 0 = escape (outlier), 1..2R+1 = residual shifted by R+1
RESIDUAL_RADIUS = 32767  # 2n-1 = 65535 bins, paper §6.3.2
#: bumped SZJX -> SZJ1 when the embedded Huffman-table serialization gained
#: its zstd/raw flag byte, so streams from the old layout fail the magic
#: check cleanly instead of erroring mid-decode
_MAGIC = b"SZJ1"
#: the device-encoded container version (DESIGN.md §3.7): byte layout is
#: identical to SZJ1 — same table, same payload bit stream, same outlier
#: section — but the quantization/Lorenzo stage ran in-graph (float32),
#: so the flag records provenance. `sz_decompress` accepts both.
DEVICE_MAGIC = b"SZJ2"


# ---------------------------------------------------------------------------
# in-graph statistics path
# ---------------------------------------------------------------------------


@dataclass
class SZStats:
    bitrate: jax.Array      # bits/value (entropy + 0.5 offset + outliers)
    psnr: jax.Array         # actual PSNR of the reconstruction
    mse: jax.Array
    recon: jax.Array        # reconstruction (error <= eb pointwise)
    outlier_frac: jax.Array


def sz_stats(x: jax.Array, eb: jax.Array | float, hist_radius: int = RESIDUAL_RADIUS) -> SZStats:
    """Exact rate/distortion of the SZ path, computed in-graph."""
    xf = x.astype(jnp.float32)
    delta = 2.0 * jnp.asarray(eb, jnp.float32)
    codes = jnp.round(xf / delta)
    recon = (codes * delta).astype(jnp.float32)
    d = lorenzo_forward(codes)
    clipped = jnp.clip(d, -hist_radius, hist_radius)
    outlier = jnp.abs(d) > hist_radius
    hist = jnp.histogram(
        clipped, bins=2 * hist_radius + 1, range=(-hist_radius - 0.5, hist_radius + 0.5)
    )[0]
    p = hist.astype(jnp.float32) / jnp.maximum(hist.sum(), 1)
    ent = -jnp.sum(jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-30)), 0.0))
    ofrac = jnp.mean(outlier.astype(jnp.float32))
    # entropy + Huffman suboptimality offset (paper §6.2) + escape payload
    bitrate = ent + 0.5 + ofrac * 64.0
    err = xf - recon
    mse = jnp.mean(jnp.square(err.astype(jnp.float32)))
    vr = jnp.maximum(jnp.max(xf) - jnp.min(xf), 1e-30).astype(jnp.float32)
    psnr = -10.0 * jnp.log10(jnp.maximum(mse, 1e-60) / (vr * vr))
    return SZStats(bitrate=bitrate, psnr=psnr, mse=mse, recon=recon, outlier_frac=ofrac)


# ---------------------------------------------------------------------------
# host byte codec
# ---------------------------------------------------------------------------


def _lorenzo_fwd_np(k: np.ndarray) -> np.ndarray:
    out = k
    for ax in range(k.ndim):
        out = np.diff(out, axis=ax, prepend=np.zeros_like(np.take(out, [0], axis=ax)))
    return out


def _lorenzo_inv_np(d: np.ndarray) -> np.ndarray:
    out = d
    for ax in range(d.ndim):
        out = np.cumsum(out, axis=ax)
    return out


def sz_container(
    shape: tuple[int, ...],
    delta: float,
    table: "_entropy.HuffmanTable",
    payload: bytes,
    outliers: np.ndarray,
    *,
    magic: bytes = _MAGIC,
) -> bytes:
    """Assemble the self-describing SZ container around an already-encoded
    Huffman payload. Shared by the host Stage III (`sz_encode_residuals`)
    and the device encode tier (`core/device_encode.py`), which packs the
    same payload bits in-graph (DESIGN.md §3.7) and only assembles here."""
    outliers = np.asarray(outliers, dtype=np.int64)
    size = int(np.prod(shape, dtype=np.int64)) if shape else 1
    hdr = struct.pack(
        "<4sBdQI", magic, len(shape), float(delta), size, len(outliers)
    ) + struct.pack(f"<{len(shape)}q", *shape)
    tbl = table.to_bytes()
    return b"".join(
        [
            hdr,
            struct.pack("<I", len(tbl)), tbl,
            struct.pack("<Q", len(payload)), payload,
            outliers.tobytes(),
        ]
    )


def sz_encode_residuals(
    d: np.ndarray, shape: tuple[int, ...], delta: float, *, magic: bytes = _MAGIC
) -> bytes:
    """Stage III on precomputed Lorenzo residuals: symbols, Huffman table,
    payload, outlier section, container. Split from `sz_compress` so the
    device-encode parity suite can run the host coder on *device-computed*
    residuals and compare streams byte for byte (DESIGN.md §3.7)."""
    d = np.asarray(d).reshape(-1)
    with TraceAnnotation("repro.sz.table", symbols=d.size):
        d = d.astype(np.int64)
        esc_mask = np.abs(d) > RESIDUAL_RADIUS
        syms = np.where(esc_mask, 0, d + RESIDUAL_RADIUS + 1).astype(np.int64)
        freqs = np.bincount(syms, minlength=2 * RESIDUAL_RADIUS + 2)
        table = _entropy.build_table(freqs)
        outliers = d[esc_mask]
    with TraceAnnotation("repro.sz.pack"):
        payload = _entropy.encode(syms, table)
    with TraceAnnotation("repro.sz.container", outliers=outliers.size):
        return sz_container(shape, delta, table, payload, outliers, magic=magic)


def sz_compress(x: np.ndarray, eb: float) -> bytes:
    """Error-bounded compression to a self-describing byte stream."""
    x = np.asarray(x, dtype=np.float32)
    assert eb > 0, "error bound must be positive"
    delta = 2.0 * float(eb)
    with TraceAnnotation("repro.sz.quantize"):
        codes = np.round(np.nan_to_num(x.astype(np.float64) / delta)).astype(np.int64)
        d = _lorenzo_fwd_np(codes)
    return sz_encode_residuals(d, x.shape, delta)


def sz_decompress(buf: bytes) -> np.ndarray:
    off = 0
    magic, ndim, delta, size, n_out = struct.unpack_from("<4sBdQI", buf, off)
    assert magic in (_MAGIC, DEVICE_MAGIC), (
        "not an SZJ1/SZJ2 stream (old/foreign format?)"
    )
    off += struct.calcsize("<4sBdQI")
    shape = struct.unpack_from(f"<{ndim}q", buf, off)
    off += 8 * ndim
    (tbl_len,) = struct.unpack_from("<I", buf, off)
    off += 4
    with TraceAnnotation("repro.sz.unpack"):
        table = _entropy.HuffmanTable.from_bytes(buf[off : off + tbl_len])
        off += tbl_len
        (pay_len,) = struct.unpack_from("<Q", buf, off)
        off += 8
        syms = _entropy.decode(buf[off : off + pay_len], table, size)
        off += pay_len
    with TraceAnnotation("repro.sz.reconstruct"):
        outliers = np.frombuffer(buf[off : off + 8 * n_out], dtype=np.int64)
        d = syms - (RESIDUAL_RADIUS + 1)
        esc = syms == 0
        d[esc] = outliers
        codes = _lorenzo_inv_np(d.reshape(shape))
        return (codes.astype(np.float64) * delta).astype(np.float32)


def sz_compressed_bits(buf: bytes) -> int:
    return 8 * len(buf)
