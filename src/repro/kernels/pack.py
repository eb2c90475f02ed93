"""Device-resident bitstream packing: the uint32 word-arena packer (DESIGN.md §3.7).

Stage III's byte emission used to be the one host-only step of the save
path; this module is the jit-safe core that moves it in-graph. Both device
encoders (`core/device_encode.py`) reduce their variable-length emissions
to the same primitive: a *monotone* sequence of (bit-offset, value, length)
writes into a preallocated uint32 word arena — no data-dependent control
flow, no data-dependent shapes. `pack_codes` realizes it as two
scatter-adds (each write lands in at most two words) and tolerates
zero-length writes, so it merges the ZFP chunk emitter's mostly-empty
slot grid as well as the SZ Huffman stream. On a v5e the scatter form
packs a 100x500x500 SZ field's 2^24-word arena in 0.44 s, where a
word-major gather form (each word searching for the window of codes that
overlap it) took 19 s (PERF.md §6).

Layout contract (what makes the arena byte-compatible with the host
coders): bit `b` of the stream lives in word `b >> 5` at bit `31 - (b & 31)`
— MSB-first within each big-endian word — so `words.byteswap().tobytes()`
truncated to `ceil(nbits/8)` is exactly what `np.packbits` would have
produced from the same bit sequence. The decoders (`core/sz.py`,
`core/zfp.py`) never change.

Everything is uint32-only: the repo runs with x64 disabled, and write
lengths capped at 32 (`MAX_CODE_LEN` is 24 for SZ; ZFP chunk parts are
right-aligned 32-bit halves) keep every shift strictly inside [0, 32).
Offsets are exclusive prefix sums, so writes to the same word never
collide on a bit — scatter `add` is `or` here by construction. Out-of-arena
writes (the rate model under-estimated) fall in `mode='drop'`: the arena
can *truncate* but never corrupt, and the caller detects truncation from
the true total bit count (DESIGN.md §3.7 fallback rules).

On TPU this lowers to XLA scatters with sorted indices; on CPU the same
program runs through the XLA:CPU path (the kernels' interpret tier,
DESIGN.md §3.3), which is what the `device_encode_speedup` bench gate
ratio measures.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

#: arena word width; the packer's only unit
WORD_BITS = 32


def arena_words(nbits: int, min_words: int = 64) -> int:
    """Arena size (in uint32 words) for a bit budget: the next power of two
    at or above `ceil(nbits/32)`. The pow2 bucketing bounds the jit compile
    cache exactly like the block-batch bucketing of DESIGN.md §1 — arenas
    of the same bucket share one compiled packer."""
    need = max(int(min_words), -(-int(nbits) // WORD_BITS))
    return 1 << int(np.ceil(np.log2(need)))


def pack_codes(
    codes: jnp.ndarray,
    lens: jnp.ndarray,
    offsets: jnp.ndarray,
    n_words: int,
) -> jnp.ndarray:
    """Pack variable-length codes (MSB-first) into a fresh word arena
    (scatter form).

    Args:
      codes: (N,) uint32 — each value's low `lens[i]` bits are the codeword.
      lens: (N,) int32 in [0, 32] — 0 emits nothing (dead slots are free).
      offsets: (N,) int32 — exclusive prefix sum of `lens`: bit offset of
        each code in the stream (monotone; the §3.7 prefix-sum layout).
      n_words: static arena size (`arena_words`).

    Returns the (n_words,) uint32 arena. A code lands in at most two words:
    `hi` carries the upper `len - spill` bits into word `off >> 5`, `lo`
    the remaining `spill` bits into the next word. All shifts stay in
    [0, 32) — `spill <= 31` because `len <= 32`.
    """
    codes = codes.astype(jnp.uint32)
    lens = lens.astype(jnp.int32)
    offsets = offsets.astype(jnp.int32)
    pos = offsets & (WORD_BITS - 1)
    w0 = offsets >> 5
    end = pos + lens
    spill = jnp.maximum(end - WORD_BITS, 0)
    hi_shift = jnp.clip(WORD_BITS - end, 0, WORD_BITS - 1).astype(jnp.uint32)
    hi = (codes >> spill.astype(jnp.uint32)) << hi_shift
    lo_shift = jnp.clip(WORD_BITS - spill, 0, WORD_BITS - 1).astype(jnp.uint32)
    lo = jnp.where(spill > 0, codes << lo_shift, jnp.uint32(0))
    live = lens > 0
    hi = jnp.where(live, hi, jnp.uint32(0))
    lo = jnp.where(live, lo, jnp.uint32(0))
    words = jnp.zeros((n_words,), jnp.uint32)
    words = words.at[w0].add(hi, mode="drop", indices_are_sorted=True)
    words = words.at[w0 + 1].add(lo, mode="drop", indices_are_sorted=True)
    return words


def words_to_bytes(words: np.ndarray, nbits: int) -> bytes:
    """Host finalizer: big-endian word arena -> the exact `np.packbits`
    byte stream for `nbits` bits. Bits past `nbits` were never written
    (the arena starts zeroed), so truncation is safe and the result is
    byte-identical to the host coders' payloads."""
    nbytes = -(-int(nbits) // 8)
    return np.asarray(words, dtype=np.uint32).byteswap().tobytes()[:nbytes]


__all__ = [
    "WORD_BITS",
    "arena_words",
    "pack_codes",
    "words_to_bytes",
]
