"""Device-resident Stage III: in-graph bitstream encode (DESIGN.md §3.7).

The PR 4 kernel tier stopped at quantized codes + bit accounting and
shipped raw codes to the host coder — the last host roundtrip on the save
path (the old DESIGN.md §3.6 rule). This module finishes Stage III
in-graph for both codecs, emitting into the `kernels/pack.py` word arena
so the only transfer per field is one `jax.device_get` of packed words
(plus the small per-block sidecars the containers carry anyway):

* **SZ** — two-pass device Huffman: pass 1 jits quantize + Lorenzo
  (`kernels/ops.lorenzo_encode`, the Pallas tier for 2-D/3-D) and a
  65536-bin histogram; the host builds the canonical code table from the
  fetched histogram (tiny — `entropy.build_table` on O(2^16) symbols) and
  knows the exact payload size (`sum(freqs * lens)`); pass 2 jits the
  table-lookup code/length gather, the exclusive prefix-sum of lengths,
  and the word-major `pack_codes_gather`. Escape literals ride the same
  launch: a rank-indexed `searchsorted` gather compacts outlier residuals
  into the container's int64 section. The stream is the SZJ1 layout under
  the versioned `SZJ2` magic (`sz.DEVICE_MAGIC`) — `sz_decompress`
  decodes both.

* **ZFP** — in-kernel plane emission: blockize/align/transform reuse the
  jit-safe §3 pieces; the arena is pre-sized from the closed-form
  `embedded.block_bits` rate model (the buffer-sizing idea of the
  black-box ratio-prediction line, PAPERS.md arXiv 2305.08801), and the
  plane-sectioned k-prefix layout of `zfp.py` is reproduced exactly in
  closed form over per-coefficient bit lengths: each (plane, block) emits
  seven right-aligned <= 32-bit chunks (refinement, the w-bit k field,
  test bits, signs — split at rank 32), whose values come from masked
  shift-sum reductions and whose offsets from one prefix sum, merged by
  the scatter `pack_codes` (see `_zfp_pass2b`). The container is the
  unchanged ZFJX format — the host decoder needs no changes.

Parity contract (what the tests and the `device_encode_parity` gate
check): fed the SAME quantized codes, the device packer and the host
Stage III produce byte-identical streams (`sz.sz_encode_residuals` /
`zfp.zfp_encode_quantized` exist exactly for this). The integrated path
quantizes in float32 (like every in-graph path since `sz_stats` /
`zfp_stats`), so codes can differ from the float64 host quantizer at
rounding boundaries — the reconstruction honors the same pointwise bound
either way.

Fallback rules (DESIGN.md §3.7) — `None` from any encoder means "use the
host coder", never a truncated stream:

* the rate model under-estimated and the emitted bits overran the arena
  (`pack` drops out-of-range writes, and the true total is checked);
* code magnitudes exceed float32-exact integer range (2^23 for SZ codes,
  2^24 for ZFP plane magnitudes — the >= 24 bits/value regime where
  selection picks raw anyway);
* non-finite values, zero-size fields, or streams past int32 bit offsets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, pack

from . import entropy as _entropy
from . import sz as _sz
from . import zfp as _zfp
from .embedded import align_blocks
from .transforms import blockize, bot_linf_gain, bot_matrix, block_transform_nd

#: SZ symbol alphabet (escape + shifted residuals), as in core/sz.py
N_SYMBOLS = 2 * _sz.RESIDUAL_RADIUS + 2
#: float32 keeps integers exact below 2^24; SZ codes also pass through
#: Lorenzo corner sums (2^ndim terms), so the code guard is 2^23
_SZ_CODE_LIMIT = 2.0**23
_ZFP_MAG_LIMIT = 2.0**24
#: bit offsets are int32 prefix sums
_MAX_STREAM_BITS = 2**31 - 1


def _degree_order(nd: int) -> np.ndarray:
    idx = np.indices((4,) * nd).reshape(nd, -1)
    return np.argsort(idx.sum(axis=0), kind="stable")


# ---------------------------------------------------------------------------
# SZ: two-pass device Huffman
# ---------------------------------------------------------------------------


@jax.jit
def _sz_pass1(x, eb):
    """Quantize + Lorenzo (Pallas tier for 2-D/3-D) -> residuals, symbols
    (flat: pass 2 works on the stream order, and handing it a tiled n-D
    array makes its TPU compile several times slower), histogram, and the
    |x| max for the float32-exactness guard."""
    d = ops.lorenzo_encode(x, eb)
    syms = jnp.where(
        jnp.abs(d) > _sz.RESIDUAL_RADIUS, 0, d + _sz.RESIDUAL_RADIUS + 1
    ).astype(jnp.int32).reshape(-1)
    hist = jnp.bincount(syms, length=N_SYMBOLS)
    amax = jnp.max(jnp.abs(x))
    return d, syms, hist, amax


@functools.partial(jax.jit, static_argnames=("n_words", "esc_cap", "window"))
def _sz_pass2(syms, d, lut_codes, lut_lens, *, n_words, esc_cap, window):
    """Table-lookup gather + prefix-sum pack, and escape compaction.

    The packer is the gather form (`pack_codes_gather`): every emitted
    symbol has a code (`len >= 1`), so each arena word overlaps a bounded
    window of codes. Escapes compact by rank through `searchsorted` on the
    escape-count prefix sum — `esc_cap` gathers instead of a full-length
    scatter."""
    lens = lut_lens[syms]
    codes = lut_codes[syms]
    offsets = jnp.cumsum(lens) - lens  # exclusive
    words = pack.pack_codes_gather(codes, lens, offsets, n_words, window)
    esc_rank = jnp.cumsum((syms == 0).astype(jnp.int32))
    tgt = jnp.arange(1, max(esc_cap, 1) + 1, dtype=jnp.int32)
    idx = jnp.clip(
        jnp.searchsorted(esc_rank, tgt, side="left"), 0, syms.shape[0] - 1
    )
    # lanes past the true escape count gather garbage; the host reads
    # exactly the first n_esc
    escapes = d.reshape(-1)[idx].astype(jnp.int32)
    return words, escapes


def sz_device_residuals(x, eb: float) -> np.ndarray:
    """Device-computed Lorenzo residuals (parity/debug surface): the exact
    codes the device encoder packs, for feeding `sz.sz_encode_residuals`."""
    d, _, _, _ = _sz_pass1(jnp.asarray(x, jnp.float32), jnp.float32(eb))
    return np.asarray(jax.device_get(d))


def sz_encode_device(x, eb: float) -> bytes | None:
    """Device-resident SZ encode -> SZJ2 container bytes, or None (host
    fallback). `x` is the folded f32 view; `eb` the SZ bound (eb_sz)."""
    shape = tuple(np.shape(x))
    size = int(np.prod(shape, dtype=np.int64)) if shape else 0
    if size == 0 or eb <= 0:
        return None
    delta32 = np.float32(2.0) * np.float32(eb)
    if not np.isfinite(float(delta32)) or float(delta32) <= 0.0:
        return None
    x32 = jnp.asarray(x, jnp.float32)
    d, syms, hist, amax = _sz_pass1(x32, jnp.float32(eb))
    freqs, amax = jax.device_get((hist, amax))
    amax = float(amax)
    if not np.isfinite(amax) or amax / float(delta32) >= _SZ_CODE_LIMIT:
        return None
    freqs = np.asarray(freqs, dtype=np.int64)
    table = _entropy.build_table(freqs)
    payload_bits = int((freqs * table.lens.astype(np.int64)).sum())
    if payload_bits > _MAX_STREAM_BITS:
        return None
    n_esc = int(freqs[0])
    n_words = pack.arena_words(payload_bits)
    esc_cap = pack.arena_words(32 * n_esc) if n_esc else 0
    # payload_bits is exact (sum(freqs*lens)), so unlike ZFP's modeled
    # budget these can't under-size — but the drop-mode arena makes a
    # short buffer silently truncate, so guard the invariant anyway
    if 32 * n_words < payload_bits or esc_cap < n_esc:
        return None
    emitted = table.lens[(freqs > 0) & (table.lens > 0)]
    min_len = int(emitted.min()) if emitted.size else 1
    words, escapes = _sz_pass2(
        syms, d,
        jnp.asarray(table.codes.astype(np.uint32)),
        jnp.asarray(table.lens.astype(np.int32)),
        n_words=n_words, esc_cap=esc_cap,
        window=pack.gather_window(min_len),
    )
    words_np, esc_np = jax.device_get((words, escapes))
    payload = pack.words_to_bytes(words_np, payload_bits)
    outliers = np.asarray(esc_np[:n_esc], dtype=np.int64)
    # container delta is the float32 value the device divided by, so the
    # decoder multiplies by exactly the encoder's bin size
    return _sz.sz_container(
        shape, float(delta32), table, payload, outliers, magic=_sz.DEVICE_MAGIC
    )


# ---------------------------------------------------------------------------
# ZFP: model-sized arena + in-graph plane emission
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("transform",))
def _zfp_pass1(x, *, transform):
    """Blockize + exponent-align + BOT (all §3 jit-safe pieces, f32)."""
    n = x.ndim
    T = jnp.asarray(bot_matrix(transform), jnp.float32)
    blocks, _ = blockize(x.astype(jnp.float32))
    norm, e = align_blocks(blocks)
    coeffs = block_transform_nd(norm, T, n)
    return coeffs, e


@functools.partial(jax.jit, static_argnames=("nd",))
def _zfp_pass2a(coeffs, step, *, nd):
    """Quantize to plane magnitudes (degree order) + the closed-form
    `block_bits` budget that sizes the arena (DESIGN.md §3.7)."""
    bsz = 4**nd
    w = int(np.ceil(np.log2(bsz + 1)))
    nblk = coeffs.shape[0]
    c = coeffs.reshape(nblk, bsz)[:, _degree_order(nd)]
    mf = jnp.trunc(jnp.abs(c) / step[:, None])
    mmax = jnp.max(mf) if mf.size else jnp.float32(0.0)
    m = jnp.minimum(mf, 2.0**31 - 1).astype(jnp.int32)
    neg = c < 0
    mx = jnp.max(m, axis=1) if m.size else jnp.zeros((nblk,), jnp.int32)
    nsb = jnp.where(
        mx > 0,
        jnp.floor(jnp.log2(jnp.maximum(mx.astype(jnp.float32), 1.0))) + 1.0,
        0.0,
    ).astype(jnp.int32)
    nsb_c = jnp.where(
        m > 0,
        jnp.floor(jnp.log2(jnp.maximum(m.astype(jnp.float32), 1.0))) + 1.0,
        0.0,
    )
    # the block_bits payload model: w*maxplane + sum(nsb) + 2*nsig per block
    # (headers live in the e/nsb sidecars, not the packed payload)
    model = (
        w * jnp.sum(nsb.astype(jnp.float32))
        + jnp.sum(nsb_c)
        + 2.0 * jnp.sum((m > 0).astype(jnp.float32))
    )
    maxp = jnp.max(nsb) if nsb.size else jnp.int32(0)
    return m, neg, nsb, model, maxp, mmax


@functools.partial(jax.jit, static_argnames=("n_words", "n_planes"))
def _zfp_pass2b(m, neg, nsb, *, n_words, n_planes):
    """The plane-sectioned k-prefix emitter of `zfp._emit_planes`, in
    closed form over per-coefficient bit lengths (DESIGN.md §3.7).

    Instead of replaying the host's per-plane boolean-mask concatenation
    bit by bit, every plane/block/section quantity follows from one tensor
    `nc[i] = bitlength(m[i])`: at plane p, a coefficient is already
    significant iff `nc >= p+2`, becomes significant iff `nc == p+1` (and
    that equality IS the tested bit's value), and the section ranks are
    exclusive prefix counts of those masks — one int8 cumsum over the
    shared `nc >= t` tensor yields every rank for every plane. Each
    (plane, block) then emits seven right-aligned chunks of <= 32 bits
    (refinement lo/hi, the w-bit k field, test lo/hi, sign lo/hi), built
    by masked shift-sum reductions; chunk offsets are one exclusive prefix
    sum, and the scatter packer merges the mostly-empty slot grid into the
    arena. No data-dependent control flow, and ~1% of the scatter volume
    of the per-bit formulation — what makes the emitter viable on the
    2-core XLA:CPU bench host.
    """
    if n_planes == 0:
        return jnp.zeros((n_words,), jnp.uint32), jnp.int32(0)
    nblk, bsz = m.shape
    w = int(np.ceil(np.log2(bsz + 1)))
    P = n_planes
    mf = jnp.maximum(m, 1).astype(jnp.float32)
    nc = jnp.where(m > 0, jnp.floor(jnp.log2(mf)) + 1.0, 0.0).astype(jnp.int8)
    t_ax = jnp.arange(1, P + 2, dtype=jnp.int8)[:, None, None]
    ge = nc[None] >= t_ax  # (P+1, nblk, bsz)
    g8 = ge.astype(jnp.int8)
    # exclusive prefix counts; int8 suffices (bsz <= 64) and halves traffic
    C = jnp.cumsum(g8, axis=2, dtype=jnp.int8) - g8
    p_ax = jnp.arange(P, dtype=jnp.int32)[:, None, None]
    i_ax = jnp.arange(bsz, dtype=jnp.int8)[None, None, :]
    act = p_ax < nsb[None, :, None].astype(jnp.int32)
    ref = ge[1:]  # significant before plane p: nc >= p+2
    rank_ref = C[1:]
    newly = ge[:-1] & ~ge[1:]  # becomes significant at p: nc == p+1
    rank_sign = C[:-1] - C[1:]
    rank_rem = i_ax - rank_ref
    rem = act & ~ge[1:]
    k8 = jnp.max(jnp.where(newly, rank_rem + 1, 0), axis=2).astype(jnp.int8)
    cnt_rem = jnp.sum(rem, axis=2, dtype=jnp.int32)
    has_rem = act[:, :, 0] & (cnt_rem > 0)
    cnt_ref = jnp.sum(ref, axis=2, dtype=jnp.int32)
    cnt_new = jnp.sum(newly, axis=2, dtype=jnp.int32)
    refbit = ((m[None] >> p_ax) & 1).astype(jnp.uint32)
    testbit = newly.astype(jnp.uint32)  # the tested bit IS [nc == p+1]
    negb = neg[None].astype(jnp.uint32)

    def partvals(mask, bits, rank8, cnt):
        """Right-aligned values of a section's lo (ranks < 32) and hi
        (ranks >= 32) 32-bit chunks, as masked shift-sum reductions."""
        rank = rank8.astype(jnp.int32)
        expo = jnp.clip(cnt[:, :, None] - 1 - rank, 0, 63)
        sh_lo = jnp.where(cnt[:, :, None] > 32, 31 - rank, expo)
        v_lo = jnp.sum(
            jnp.where(mask & (rank8 < 32),
                      bits << jnp.clip(sh_lo, 0, 31).astype(jnp.uint32), 0),
            axis=2, dtype=jnp.uint32)
        v_hi = jnp.sum(
            jnp.where(mask & (rank8 >= 32),
                      bits << jnp.clip(expo, 0, 31).astype(jnp.uint32), 0),
            axis=2, dtype=jnp.uint32)
        return v_lo, jnp.minimum(cnt, 32), v_hi, jnp.maximum(cnt - 32, 0)

    test = rem & (rank_rem < k8[:, :, None])
    rA, rlA, rB, rlB = partvals(ref, refbit, rank_ref, cnt_ref)
    tA, tlA, tB, tlB = partvals(
        test, testbit, rank_rem, jnp.minimum(k8.astype(jnp.int32), cnt_rem))
    sA, slA, sB, slB = partvals(newly, negb, rank_sign, cnt_new)
    klen = jnp.where(has_rem, w, 0)

    def inter(a, b):
        return jnp.stack([a, b], axis=2).reshape(P, -1)

    # stream order: planes DESCENDING; per plane: block-major refinement,
    # then the k fields, then test bits, then signs — the host layout
    lens = jnp.concatenate(
        [inter(rlA, rlB), klen, inter(tlA, tlB), inter(slA, slB)],
        axis=1)[::-1].reshape(-1)
    vals = jnp.concatenate(
        [inter(rA, rB), k8.astype(jnp.uint32), inter(tA, tB), inter(sA, sB)],
        axis=1)[::-1].reshape(-1)
    offs = jnp.cumsum(lens) - lens
    total = offs[-1] + lens[-1]
    return pack.pack_codes(vals, lens, offs, n_words), total


def _zfp_step(e_np: np.ndarray, eb: float, gain_n: float) -> np.ndarray | None:
    """The power-of-two truncation step, float64, EXACTLY the formula the
    decoder (and `_prepare_blocks`) evaluates — then cast to f32 for the
    device (powers of two are exact). None when it leaves f32 range."""
    raw = eb / (np.exp2(e_np.astype(np.float64)) * gain_n)
    pexp = np.floor(np.log2(np.maximum(raw, 2.0**-60)))
    if pexp.size and (pexp.min() < -126 or pexp.max() > 127):
        return None
    return np.exp2(pexp).astype(np.float32)


def zfp_device_codes(x, eb: float, transform: str = "zfp"):
    """Device-computed quantized codes (parity/debug surface): (q, e) in
    raw block layout, for feeding `zfp.zfp_encode_quantized`."""
    x32 = jnp.asarray(x, jnp.float32)
    nd = x32.ndim
    coeffs, e = _zfp_pass1(x32, transform=transform)
    e_np = np.asarray(jax.device_get(e), dtype=np.int16)
    step = _zfp_step(e_np, eb, bot_linf_gain(transform) ** nd)
    assert step is not None, "step outside f32 range"
    # c / step is exact in f32 (power-of-two step), so the f64 trunc here
    # reproduces the device's plane magnitudes bit for bit below 2^24
    c = np.asarray(jax.device_get(coeffs), dtype=np.float64).reshape(len(e_np), -1)
    q = np.trunc(c / step.astype(np.float64)[:, None]).astype(np.int64)
    return q, e_np


def zfp_encode_device(x, eb: float, transform: str = "zfp") -> bytes | None:
    """Device-resident ZFP encode -> ZFJX container bytes, or None (host
    fallback). `x` is the folded f32 view; `eb` the absolute bound."""
    shape = tuple(np.shape(x))
    size = int(np.prod(shape, dtype=np.int64)) if shape else 0
    if size == 0 or eb <= 0 or not np.isfinite(eb):
        return None
    x32 = jnp.asarray(x, jnp.float32)
    nd = x32.ndim
    bsz = 4**nd
    w = int(np.ceil(np.log2(bsz + 1)))
    padded = tuple(s + (-s) % 4 for s in shape)
    coeffs, e = _zfp_pass1(x32, transform=transform)
    e_np = np.asarray(jax.device_get(e), dtype=np.int16)
    nblk = len(e_np)
    step = _zfp_step(e_np, eb, bot_linf_gain(transform) ** nd)
    if step is None:
        return None
    m, neg, nsb, model, maxp, mmax = _zfp_pass2a(
        coeffs, jnp.asarray(step), nd=nd
    )
    model, maxp, mmax = jax.device_get((model, maxp, mmax))
    if not np.isfinite(float(mmax)) or float(mmax) >= _ZFP_MAG_LIMIT:
        return None
    n_planes = min(24, -(-int(maxp) // 4) * 4) if int(maxp) else 0
    # int32 bit-offset headroom for the worst-case emission of this launch
    if nblk * (3 * bsz + w) * max(n_planes, 1) > _MAX_STREAM_BITS:
        return None
    n_words = pack.arena_words(float(model))
    words, total = _zfp_pass2b(m, neg, nsb, n_words=n_words, n_planes=n_planes)
    words_np, total_bits, nsb_np = jax.device_get((words, total, nsb))
    total_bits = int(total_bits)
    if total_bits > 32 * n_words:
        # the block_bits model under-estimated past the pow2 slack: the
        # arena dropped bits — clean per-field host fallback, never a
        # truncated stream (DESIGN.md §3.7)
        return None
    payload = pack.words_to_bytes(words_np, total_bits)
    return _zfp.zfp_container(
        shape, padded, float(eb), transform, e_np,
        np.asarray(nsb_np, dtype=np.uint8), total_bits, payload,
    )


# ---------------------------------------------------------------------------
# registry surface
# ---------------------------------------------------------------------------


def encode_field_device(view32, sel) -> bytes | None:
    """Capability entry point behind the codec registry (`device_encode`):
    dispatch one folded f32 view to the device encoder for its selected
    codec. None -> caller uses the host coder."""
    if sel.codec == "sz":
        return sz_encode_device(view32, sel.eb_sz)
    if sel.codec == "zfp":
        return zfp_encode_device(view32, sel.eb_abs)
    return None


__all__ = [
    "encode_field_device",
    "sz_device_residuals",
    "sz_encode_device",
    "zfp_device_codes",
    "zfp_encode_device",
]
