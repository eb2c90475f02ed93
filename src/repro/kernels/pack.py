"""Device-resident bitstream packing: the uint32 word-arena packer (DESIGN.md §3.7).

Stage III's byte emission used to be the one host-only step of the save
path; this module is the jit-safe core that moves it in-graph. Both device
encoders (`core/device_encode.py`) reduce their variable-length emissions
to the same primitive: a *monotone* sequence of (bit-offset, value, length)
writes into a preallocated uint32 word arena — no data-dependent control
flow, no data-dependent shapes. Two realizations of that primitive live
here, chosen by what the caller can promise:

* `pack_codes` — scatter form: each write lands in at most two words via
  masked shift/or scatter-adds. Tolerates zero-length writes, so it merges
  the ZFP chunk emitter's mostly-empty slot grid.
* `pack_codes_gather` — gather form: each *word* sums the shifted
  contributions of the bounded window of codes that can overlap it
  (`searchsorted` on the offset prefix sum finds the first). Requires
  every length >= 1 — the SZ Huffman stream qualifies (every emitted
  symbol has a code) — and on the 2-core XLA:CPU backend it beats the
  scatter form by avoiding the serialized scatter loop entirely.

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

On TPU these lower to XLA scatters/gathers over VMEM-resident arenas; on
CPU the same program runs through the XLA:CPU path (the kernels' interpret
tier, DESIGN.md §3.3), which is what the `device_encode_speedup` bench
gate ratio measures.
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


def gather_window(min_len: int) -> int:
    """Static gather window for `pack_codes_gather`: an upper bound on how
    many codes can overlap one 32-bit word when every code is at least
    `min_len` bits — one straddling the word start plus `32 // min_len`
    starting inside it, +1 slack. Bucketed to a small set so streams with
    different tables share compiled packers (the §1 bucketing rule)."""
    need = WORD_BITS // max(int(min_len), 1) + 2
    for cap in (6, 10, 18, 34):
        if need <= cap:
            return cap
    return 34


def pack_codes_gather(
    codes: jnp.ndarray,
    lens: jnp.ndarray,
    offsets: jnp.ndarray,
    n_words: int,
    window: int,
) -> jnp.ndarray:
    """Pack variable-length codes (MSB-first) into a fresh word arena
    (gather form): word `i` is the OR (sum — bits never collide) of the
    shifted contributions of the codes overlapping bits [32i, 32i+32).

    Contract: every `lens[i]` is in [1, 32] (no dead slots — the window
    bound breaks otherwise) and `window >= 32 // min(lens) + 2`
    (`gather_window`). `offsets` is the exclusive prefix sum of `lens`.
    Words past the last code read dead lanes and come out zero, so the
    pow2 arena slack is harmless.

    The window is walked one slot at a time over (n_words,) vectors: a
    (n_words, window) temporary would pad its short minor dim to a full
    128-lane tile on TPU (21x the bytes at window 6 — 32 GiB for a 512^3
    field's arena).
    """
    n = codes.shape[0]
    starts = jnp.arange(n_words, dtype=jnp.int32) * WORD_BITS
    first = jnp.searchsorted(offsets, starts, side="right").astype(jnp.int32) - 1
    first = jnp.clip(first, 0, max(n - 1, 0))
    words = jnp.zeros((n_words,), jnp.uint32)
    for k in range(window):
        j = first + k
        jc = jnp.minimum(j, max(n - 1, 0))
        off = offsets[jc]
        ln = lens[jc].astype(jnp.int32)
        c = codes[jc].astype(jnp.uint32)
        # t: how many bits of code j extend past this word's start
        t = off + ln - starts
        live = (j < n) & (t > 0) & (off < starts + WORD_BITS)
        contrib = jnp.where(
            t > WORD_BITS,
            c >> jnp.clip(t - WORD_BITS, 0, WORD_BITS - 1).astype(jnp.uint32),
            c << jnp.clip(WORD_BITS - t, 0, WORD_BITS - 1).astype(jnp.uint32),
        )
        words = words + jnp.where(live, contrib, jnp.uint32(0))
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
    "gather_window",
    "pack_codes",
    "pack_codes_gather",
    "words_to_bytes",
]
