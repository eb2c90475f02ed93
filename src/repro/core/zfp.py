"""ZFP-style transform-based error-bounded lossy compressor (paper §2, §5.2).

Pipeline (Fig. 1): 4^n blocking -> exponent alignment -> fixed point ->
block orthogonal transform T(t) -> bit-plane embedded coding.

Two paths, mirroring sz.py:
  * `zfp_stats`     — jnp/jit-safe reconstruction + exact rate/distortion.
  * `zfp_compress` / `zfp_decompress` — host numpy byte codec with a real,
    decodable, *plane-sectioned group-tested* embedded coder (DESIGN.md §3.2):
    the bit stream is laid out plane-major across all blocks so both encode
    and decode are fully vectorized over blocks (TPU/SIMD-friendly layout,
    unlike ZFP's per-block serial group testing — same rate regime).

Pointwise guarantee: |x - x~| <= eb via the conservative plane cutoff
(`embedded.plane_step`), which is exactly why ZFP "over-preserves" error
relative to the bound (paper §6.4) and thus reaches a higher PSNR than SZ
at the same eb.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .embedded import (
    align_blocks,
    exact_coder_bits,
    plane_step,
    reconstruct_truncated,
)
from .transforms import blockize, bot_linf_gain, bot_matrix, block_transform_nd, unblockize

_MAGIC = b"ZFJX"


# ---------------------------------------------------------------------------
# in-graph statistics path
# ---------------------------------------------------------------------------


@dataclass
class ZFPStats:
    bitrate: jax.Array
    psnr: jax.Array
    mse: jax.Array
    recon: jax.Array
    mean_nsb: jax.Array  # the paper's n_sb-bar estimate target


def zfp_stats(x: jax.Array, eb: jax.Array | float, transform: str = "zfp") -> ZFPStats:
    """Exact rate/distortion of the ZFP path, computed in-graph."""
    xf = x.astype(jnp.float32)
    n = xf.ndim
    T = bot_matrix(transform)
    gain_n = bot_linf_gain(transform) ** n
    blocks, padded = blockize(xf)
    norm, e = align_blocks(blocks)
    coeffs = block_transform_nd(norm, jnp.asarray(T, jnp.float32), n)
    step = plane_step(jnp.asarray(eb, jnp.float32), e, gain_n)
    rec_coeffs = reconstruct_truncated(coeffs, step)
    total_bits = exact_coder_bits(coeffs, step)
    rec_norm = block_transform_nd(rec_coeffs, jnp.asarray(T, jnp.float32), n, inverse=True)
    shape = (-1,) + (1,) * n
    rec_blocks = rec_norm * jnp.exp2(e.astype(jnp.float32)).reshape(shape)
    recon = unblockize(rec_blocks, padded, xf.shape)
    from .embedded import significant_bits

    nsb = significant_bits(coeffs, step)
    err = xf - recon
    mse = jnp.mean(jnp.square(err.astype(jnp.float32)))
    vr = jnp.maximum(jnp.max(xf) - jnp.min(xf), 1e-30).astype(jnp.float32)
    psnr = -10.0 * jnp.log10(jnp.maximum(mse, 1e-60) / (vr * vr))
    bitrate = total_bits / xf.size
    return ZFPStats(bitrate=bitrate, psnr=psnr, mse=mse, recon=recon, mean_nsb=jnp.mean(nsb))


# ---------------------------------------------------------------------------
# host byte codec
# ---------------------------------------------------------------------------


#: blocks per float64 pass of the host coder: bounds its temporaries to a
#: few hundred MB however large the field (every block is independent)
_CHUNK_BLOCKS = 1 << 18


def _chunks(nblk: int):
    return ((lo, min(lo + _CHUNK_BLOCKS, nblk)) for lo in range(0, nblk, _CHUNK_BLOCKS))


def _transform_blocks(blocks: np.ndarray, T: np.ndarray) -> np.ndarray:
    """T along every block axis of (nblk, 4, ..., 4) float64 blocks."""
    for axis in range(1, blocks.ndim):
        blocks = np.moveaxis(np.tensordot(blocks, T, axes=[[axis], [1]]), -1, axis)
    return blocks


def _plane_step(e: np.ndarray, eb: float, gain_n: float) -> np.ndarray:
    raw = eb / (np.exp2(e.astype(np.float64)) * gain_n)
    return np.exp2(np.floor(np.log2(np.maximum(raw, 2.0**-60))))


def _prepare_blocks(x: np.ndarray, eb: float, transform: str):
    n = x.ndim
    T = bot_matrix(transform)  # float64
    gain_n = bot_linf_gain(transform) ** n
    blocks, padded = blockize(jnp.asarray(x, jnp.float32))
    blocks = np.asarray(blocks)
    nblk = blocks.shape[0]
    e = np.empty(nblk, np.int16)
    q = np.empty((nblk, 4**n), np.int64)
    for lo, hi in _chunks(nblk):
        b = blocks[lo:hi].astype(np.float64)
        mx = np.maximum(np.abs(b).reshape(hi - lo, -1).max(axis=1), 1e-30)
        e[lo:hi] = np.ceil(np.log2(mx))
        norm = b * np.exp2(-e[lo:hi].astype(np.float64)).reshape((-1,) + (1,) * n)
        coeffs = _transform_blocks(norm, T).reshape(hi - lo, -1)
        q[lo:hi] = np.trunc(coeffs / _plane_step(e[lo:hi], eb, gain_n)[:, None])
    return q, e, _plane_step(e, eb, gain_n), padded, gain_n, T


def _degree_order(nd: int) -> np.ndarray:
    """ZFP's total-degree coefficient ordering within a 4^nd block: low-degree
    (high-energy) coefficients first, so the significance staircase is
    monotone-ish and the k-prefix coder below stays near n_sb-bar bits."""
    idx = np.indices((4,) * nd).reshape(nd, -1)
    degree = idx.sum(axis=0)
    return np.argsort(degree, kind="stable")


def _k_width(bsz: int) -> int:
    """Bits to encode k in [0, bsz]."""
    return int(np.ceil(np.log2(bsz + 1)))


def _rank_dtype(bsz: int):
    """Smallest integer type that holds a 1-based rank (and k) in a block."""
    return np.int8 if bsz < 127 else np.int16


def _active_rows(nsb: np.ndarray, p: int) -> np.ndarray | None:
    """Indices of the blocks still coded at plane p, or None when all are
    (the common case: whole arrays then stand in for row gathers)."""
    active = nsb > p
    return None if active.all() else np.flatnonzero(active)


def _emit_planes(m: np.ndarray, neg: np.ndarray, nsb: np.ndarray) -> list[np.ndarray]:
    """Plane-major, degree-ordered k-prefix significance coding.

    Per plane & block: refinement bits of significant coeffs; a fixed-width
    k = 1 + rank of the last newly-significant remaining coefficient (0 if
    none); significance bits of the first k remaining coefficients only;
    signs of the newly significant. Vectorized over the blocks still coded
    at each plane (m must already be in degree order).
    """
    parts: list[np.ndarray] = []
    nblk, bsz = m.shape
    w = _k_width(bsz)
    rdt = _rank_dtype(bsz)
    kshift = np.arange(w - 1, -1, -1, dtype=rdt)
    maxp = int(nsb.max()) if nsb.size else 0
    for p in range(maxp - 1, -1, -1):
        rows = _active_rows(nsb, p)
        mp, ng = (m, neg) if rows is None else (m[rows], neg[rows])
        sig_prev = (mp >> (p + 1)) != 0
        bit_p = ((mp >> p) & 1).astype(bool)
        # 1) refinement bits of already-significant coefficients
        parts.append(bit_p[sig_prev].view(np.uint8))
        # 2) k per block with remaining coeffs (fixed width w)
        rem = ~sig_prev
        rank = np.cumsum(rem, axis=1, dtype=rdt)  # 1-based rank, valid on rem
        newly = rem & bit_p
        k = np.max(np.where(newly, rank, rdt(0)), axis=1)
        has_rem = rank[:, -1] > 0
        kb = ((k[has_rem, None] >> kshift[None, :]) & 1).astype(np.uint8)
        parts.append(kb.reshape(-1))
        # 3) significance bits of the first k remaining coefficients
        test = rem & (rank <= k[:, None])
        parts.append(bit_p[test].view(np.uint8))
        # 4) signs of newly-significant coefficients
        parts.append(ng[newly].view(np.uint8))
    return parts


def _read_planes(bits: np.ndarray, pos: int, nblk: int, bsz: int, nsb: np.ndarray):
    """Inverse of `_emit_planes` over 0/1 `bits` from `pos` -> (magnitudes,
    signs, end position)."""
    maxp = int(nsb.max()) if nsb.size else 0
    m = np.zeros((nblk, bsz), dtype=np.int64 if maxp > 30 else np.int32)
    neg = np.zeros((nblk, bsz), dtype=bool)
    bits = bits.astype(np.uint8, copy=False)
    w = _k_width(bsz)
    rdt = _rank_dtype(bsz)
    kweights = (1 << np.arange(w - 1, -1, -1)).astype(rdt)
    for p in range(maxp - 1, -1, -1):
        rows = _active_rows(nsb, p)
        mp = m if rows is None else m[rows]
        sig_prev = mp != 0  # mp holds the bits above plane p
        mp <<= 1
        # 1) refinement
        nref = int(np.count_nonzero(sig_prev))
        if nref:
            mp[sig_prev] |= bits[pos : pos + nref]
        pos += nref
        # 2) k values
        rem = ~sig_prev
        rank = np.cumsum(rem, axis=1, dtype=rdt)
        has_rem = rank[:, -1] > 0
        ngrp = int(np.count_nonzero(has_rem))
        k = np.zeros(len(mp), dtype=rdt)
        if ngrp:
            kb = bits[pos : pos + ngrp * w].reshape(ngrp, w).astype(rdt)
            k[has_rem] = kb @ kweights
        pos += ngrp * w
        # 3) significance bits of the first k remaining coefficients
        test = rem & (rank <= k[:, None])
        nbm = int(np.count_nonzero(test))
        newly = np.zeros(mp.shape, dtype=bool)
        if nbm:
            bmb = bits[pos : pos + nbm]
            mp[test] |= bmb
            newly[test] = bmb.view(bool)
        pos += nbm
        # 4) signs
        nnew = int(np.count_nonzero(newly))
        ng = neg if rows is None else neg[rows]
        if nnew:
            ng[newly] = bits[pos : pos + nnew].view(bool)
        pos += nnew
        if rows is not None:
            m[rows], neg[rows] = mp, ng
    return m, neg, pos


def zfp_container(
    shape: tuple[int, ...],
    padded: tuple[int, ...],
    eb: float,
    transform: str,
    e: np.ndarray,
    nsb: np.ndarray,
    nbits: int,
    payload: bytes,
) -> bytes:
    """Assemble the ZFJX container around an already-packed plane payload.
    Shared by the host Stage III (`zfp_encode_quantized`) and the device
    encode tier (`core/device_encode.py`), whose in-graph plane emitter
    produces the identical plane-major bit stream (DESIGN.md §3.7)."""
    n = len(shape)
    hdr = struct.pack("<4sBdQ", _MAGIC, n, float(eb), len(e)) + struct.pack(
        f"<{n}q{n}q", *shape, *padded
    )
    return b"".join(
        [
            hdr,
            transform.encode().ljust(16, b"\0"),
            np.asarray(e, np.int16).tobytes(),
            np.asarray(nsb, np.uint8).tobytes(),
            struct.pack("<Q", int(nbits)),
            payload,
        ]
    )


def zfp_encode_quantized(
    q: np.ndarray,
    e: np.ndarray,
    shape: tuple[int, ...],
    padded: tuple[int, ...],
    eb: float,
    transform: str = "zfp",
) -> bytes:
    """Stage III on precomputed quantized block coefficients: degree
    ordering, plane-sectioned emission, container. `q` is (nblk, 4^n) in
    *raw* (pre-degree-order) layout, `e` the per-block exponents. Split
    from `zfp_compress` so the device-encode parity suite can run the host
    coder on *device-computed* codes and diff streams byte for byte
    (DESIGN.md §3.7)."""
    n = len(shape)
    q = np.asarray(q, dtype=np.int64).reshape(len(e), 4**n)
    order = _degree_order(n)
    q = q[:, order]  # degree-ordered layout for the k-prefix coder
    m = np.abs(q)
    if m.size and m.max() < 2**31:
        m = m.astype(np.int32)  # halves the plane coder's temporaries
    neg = q < 0
    del q
    mx = m.max(axis=1) if m.size else np.zeros(0, dtype=np.int64)
    nsb = np.zeros(len(m), dtype=np.uint8)
    nz = mx > 0
    nsb[nz] = np.floor(np.log2(mx[nz])).astype(np.uint8) + 1
    parts = _emit_planes(m, neg, nsb)
    allbits = np.concatenate(parts) if parts else np.zeros(0, dtype=np.uint8)
    payload = np.packbits(allbits).tobytes()
    return zfp_container(
        shape, padded, eb, transform, e, nsb, int(allbits.size), payload
    )


def zfp_compress(x: np.ndarray, eb: float, transform: str = "zfp") -> bytes:
    x = np.asarray(x, dtype=np.float32)
    q, e, step, padded, gain_n, _ = _prepare_blocks(x, eb, transform)
    return zfp_encode_quantized(q, e, x.shape, padded, eb, transform)


def zfp_decompress(buf: bytes) -> np.ndarray:
    off = 0
    magic, n, eb, nblk = struct.unpack_from("<4sBdQ", buf, off)
    assert magic == _MAGIC, "not a ZFJX stream"
    off += struct.calcsize("<4sBdQ")
    dims = struct.unpack_from(f"<{n}q{n}q", buf, off)
    off += 16 * n
    shape, padded = tuple(dims[:n]), tuple(dims[n:])
    transform = buf[off : off + 16].rstrip(b"\0").decode()
    off += 16
    e = np.frombuffer(buf[off : off + 2 * nblk], dtype=np.int16)
    off += 2 * nblk
    nsb = np.frombuffer(buf[off : off + nblk], dtype=np.uint8)
    off += nblk
    (nbits,) = struct.unpack_from("<Q", buf, off)
    off += 8
    bits = np.unpackbits(np.frombuffer(buf[off:], dtype=np.uint8))[:nbits]
    bsz = 4**n
    m, neg, _ = _read_planes(bits, 0, nblk, bsz, nsb.astype(np.int64))
    del bits
    inv = np.argsort(_degree_order(n))  # undo the degree-ordered layout
    step = _plane_step(e, eb, bot_linf_gain(transform) ** n)
    Tt = bot_matrix(transform).T
    rec = np.empty((nblk,) + (4,) * n, np.float32)
    for lo, hi in _chunks(nblk):
        mm = m[lo:hi][:, inv]
        mag = np.where(mm > 0, (mm.astype(np.float64) + 0.5) * step[lo:hi, None], 0.0)
        coeffs = np.where(neg[lo:hi][:, inv], -mag, mag).reshape((-1,) + (4,) * n)
        rec[lo:hi] = _transform_blocks(coeffs, Tt) * np.exp2(
            e[lo:hi].astype(np.float64)
        ).reshape((-1,) + (1,) * n)
    out = unblockize(jnp.asarray(rec), padded, shape)
    return np.asarray(out, dtype=np.float32)
