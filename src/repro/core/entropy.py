"""Stage III lossless entropy coding (paper §5.1.1, Fig. 1).

Host-side (numpy) Huffman coder used by the byte-emitting SZ path, plus the
Shannon-entropy bit-rate estimator used in-graph (Eqs. (5)/(6)).

Entropy coding is byte-stream manipulation, not tensor compute, so it stays
off the accelerator (DESIGN.md §3.6); in-graph callers use `entropy_bits`.
"""

from __future__ import annotations

import heapq
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

MAX_CODE_LEN = 24
ESCAPE = 0  # symbol 0 of the shifted alphabet is the escape symbol


def _zstd():
    """Optional: zstandard shrinks the serialized Huffman table a bit; the
    codec must still work on a bare jax+numpy environment, so streams carry
    a flag byte and fall back to the raw table blob when it is absent."""
    try:
        import zstandard

        return zstandard
    except ImportError:
        return None


def entropy_bits(hist: np.ndarray) -> float:
    """Shannon entropy (bits/value) of a histogram — Eq. (5)."""
    p = hist.astype(np.float64)
    tot = p.sum()
    if tot <= 0:
        return 0.0
    p = p[p > 0] / tot
    return float(-(p * np.log2(p)).sum())


# ---------------------------------------------------------------------------
# Canonical Huffman
# ---------------------------------------------------------------------------


def _code_lengths(freqs: np.ndarray) -> np.ndarray:
    """Huffman code lengths; dampen frequencies until max length fits."""
    f = freqs.astype(np.int64).copy()
    while True:
        lens = _huffman_lengths(f)
        if lens.max(initial=0) <= MAX_CODE_LEN:
            return lens
        f = (f + 1) // 2  # flatten the distribution, retry

def _huffman_lengths(freqs: np.ndarray) -> np.ndarray:
    sym = np.nonzero(freqs)[0]
    lens = np.zeros(len(freqs), dtype=np.int32)
    if len(sym) == 0:
        return lens
    if len(sym) == 1:
        lens[sym[0]] = 1
        return lens
    heap = [(int(freqs[s]), int(s), (int(s),)) for s in sym]
    heapq.heapify(heap)
    cnt = len(freqs)
    while len(heap) > 1:
        f1, _, g1 = heapq.heappop(heap)
        f2, _, g2 = heapq.heappop(heap)
        for s in g1 + g2:
            lens[s] += 1
        heapq.heappush(heap, (f1 + f2, cnt, g1 + g2))
        cnt += 1
    return lens


def _canonical_codes(lens: np.ndarray) -> np.ndarray:
    """Assign canonical codewords (MSB-first) from code lengths."""
    codes = np.zeros(len(lens), dtype=np.uint64)
    order = np.lexsort((np.arange(len(lens)), lens))
    code = 0
    prev_len = 0
    for s in order:
        ln = int(lens[s])
        if ln == 0:
            continue
        code <<= ln - prev_len
        codes[s] = code
        code += 1
        prev_len = ln
    return codes


@dataclass
class HuffmanTable:
    lens: np.ndarray   # (K,) int32
    codes: np.ndarray  # (K,) uint64, canonical, MSB-first

    def to_bytes(self) -> bytes:
        """Sparse serialization: (K, n_used) + flag byte + delta-coded
        symbols + lens, zstd-compressed when available (symbol runs are
        near-contiguous, lens are small), raw otherwise."""
        used = np.nonzero(self.lens)[0].astype(np.int64)
        deltas = np.diff(used, prepend=0).astype(np.uint32)
        blob = deltas.tobytes() + self.lens[used].astype(np.uint8).tobytes()
        z = _zstd()
        flag = 1 if z is not None else 0
        if z is not None:
            blob = z.ZstdCompressor(level=9).compress(blob)
        hdr = np.array([len(self.lens), len(used)], dtype=np.uint32).tobytes()
        return hdr + bytes([flag]) + blob

    @staticmethod
    def from_bytes(buf: bytes) -> "HuffmanTable":
        k, n = np.frombuffer(buf[:8], dtype=np.uint32)
        flag = buf[8]
        blob = buf[9:]
        if flag:
            z = _zstd()
            if z is None:
                raise RuntimeError(
                    "stream's Huffman table is zstd-compressed but the "
                    "'zstandard' package is not installed"
                )
            blob = z.ZstdDecompressor().decompress(blob)
        deltas = np.frombuffer(blob[: 4 * n], dtype=np.uint32).astype(np.int64)
        used = np.cumsum(deltas)
        lens = np.zeros(k, dtype=np.int32)
        lens[used] = np.frombuffer(blob[4 * n : 5 * n], dtype=np.uint8)
        return HuffmanTable(lens, _canonical_codes(lens))


def build_table(freqs: np.ndarray) -> HuffmanTable:
    lens = _code_lengths(freqs)
    return HuffmanTable(lens, _canonical_codes(lens))


#: symbols (encode) / stream bits (decode) handled per chunk: the per-bit
#: temporaries stay a few hundred MB even for 512^3 fields
_ENCODE_CHUNK = 1 << 22
_DECODE_CHUNK = 1 << 22
#: pointer-doubling levels of the decoder's code-boundary walk
_JUMP_LEVELS = 6
#: decoder chunks whose tables are built at once, one per pool thread
_DECODE_WAVE = 8


def encode(symbols: np.ndarray, table: HuffmanTable) -> bytes:
    """Vectorized Huffman encode, MSB-first, per symbol (not per bit):
    each code lands in one or two big-endian 32-bit words, and the
    disjoint bit fields of a word are summed exactly in float64."""
    freqs = np.bincount(symbols, minlength=len(table.lens))
    total = int(freqs @ table.lens.astype(np.int64))
    if total == 0:
        return b""
    words = np.zeros(-(-total // 32) + 1, dtype=np.float64)
    base = 0
    for i in range(0, len(symbols), _ENCODE_CHUNK):
        lens = table.lens[symbols[i : i + _ENCODE_CHUNK]].astype(np.int64)
        codes = table.codes[symbols[i : i + _ENCODE_CHUNK]].astype(np.int64)
        start = base + np.cumsum(lens) - lens
        base = int(start[-1] + lens[-1])
        word = start >> 5
        room = 32 - (start & 31)  # bits left in the first word
        spill = np.maximum(lens - room, 0)  # bits that go on to the next word
        head = np.where(spill > 0, codes >> spill, codes << np.maximum(room - lens, 0))
        tail = (codes & ((1 << spill) - 1)) << (32 - spill)
        w0 = int(word[0])
        words[w0 : w0 + 2 + int(word[-1] - w0)] += np.bincount(
            np.concatenate([word - w0, word + 1 - w0]),
            weights=np.concatenate([head, np.where(spill > 0, tail, 0)]).astype(np.float64),
            minlength=int(word[-1] - w0) + 2,
        )
    return words.astype(np.uint32).astype(">u4").tobytes()[: -(-total // 8)]


def _chunk_tables(quads, lo: int, hi: int, maxlen: int, lut_len: np.ndarray):
    """One chunk of stream bytes [lo, hi): the `maxlen`-bit window at every
    bit position (big-endian 32-bit loads; maxlen + 7 <= 32), the code
    length there, and pointer-doubling jumps: `jump[k][p]` is the position
    2^k codes after p, saturating at the chunk end (all numpy work, which
    releases the GIL)."""
    word = np.ascontiguousarray(quads[lo:hi]).view(">u4").reshape(-1).astype(np.uint32)
    shifts = np.arange(32 - maxlen, 24 - maxlen, -1, dtype=np.uint32)
    win = ((word[:, None] >> shifts) & np.uint32((1 << maxlen) - 1)).reshape(-1)
    step = lut_len[win]
    end = len(step)
    jump = [np.append(np.minimum(np.arange(end, dtype=np.int32) + step, end), np.int32(end))]
    for _ in range(_JUMP_LEVELS - 1):
        jump.append(jump[-1][jump[-1]])
    return win, step, jump


def _anchors(jump: list, step: np.ndarray, start: int, want: int):
    """The sequential part of a chunk's walk: every 2^(L-1)-th code
    boundary from `start`, the number of codes in the chunk (at most
    `want`), and the bit where the next chunk's first code starts."""
    end = len(step)
    span = 1 << (len(jump) - 1)
    anchors = []
    at = start
    while at < end and len(anchors) * span < want:
        anchors.append(at)
        at = int(jump[-1][at])
    # descend from the last anchor to the chunk's last code boundary
    p, extra = anchors[-1], 0
    for k in range(len(jump) - 2, -1, -1):
        if jump[k][p] < end:
            p, extra = int(jump[k][p]), extra + (1 << k)
    count = min((len(anchors) - 1) * span + extra + 1, want)
    return np.asarray(anchors, dtype=np.int32), count, p + int(step[p])


def _expand(jump: list, anchors: np.ndarray, count: int) -> np.ndarray:
    """All code boundaries of a chunk: each level interleaves the positions
    halfway between consecutive anchors."""
    pos = anchors
    for level in reversed(jump[:-1]):
        both = np.empty(2 * len(pos), dtype=np.int32)
        both[0::2] = pos
        both[1::2] = level[pos]
        pos = both
    return pos[:count]


def decode(buf: bytes, table: HuffmanTable, count: int) -> np.ndarray:
    """Table-driven canonical Huffman decode (dense 2^maxlen lookup).

    The stream is cut into chunks of `_DECODE_CHUNK` bits. Per wave of
    chunks, the window/length/jump tables (`_chunk_tables`) and then the
    code boundaries (`_expand`) and symbol gathers run on a thread pool;
    only `_anchors`, which carries each chunk's starting bit into the next,
    runs in order."""
    if count == 0:
        return np.zeros(0, dtype=np.int64)
    maxlen = int(table.lens.max())
    # dense lookup: top `maxlen` bits -> (symbol, length)
    lut_sym = np.zeros(1 << maxlen, dtype=np.int64)
    lut_len = np.zeros(1 << maxlen, dtype=np.int32)
    for s in np.nonzero(table.lens)[0]:
        ln = int(table.lens[s])
        prefix = int(table.codes[s]) << (maxlen - ln)
        lut_sym[prefix : prefix + (1 << (maxlen - ln))] = s
        lut_len[prefix : prefix + (1 << (maxlen - ln))] = ln
    data = np.concatenate([np.frombuffer(buf, dtype=np.uint8), np.zeros(8, np.uint8)])
    quads = np.lib.stride_tricks.sliding_window_view(data, 4)
    nbytes = _DECODE_CHUNK // 8
    starts = list(range(0, max(len(buf), 1), nbytes))
    out = np.empty(count, dtype=np.int64)
    n = pos = 0
    with ThreadPoolExecutor(max_workers=min(len(starts), _DECODE_WAVE)) as ex:
        for w in range(0, len(starts), _DECODE_WAVE):
            if n >= count:
                break
            los = starts[w : w + _DECODE_WAVE]
            tables = list(ex.map(
                lambda lo: _chunk_tables(quads, lo, lo + nbytes, maxlen, lut_len), los
            ))
            jobs = []
            for lo, (win, step, jump) in zip(los, tables):
                if n >= count:
                    break
                anchors, k, exit_ = _anchors(jump, step, pos - 8 * lo, count - n)
                pos = 8 * lo + exit_
                jobs.append((win, jump, anchors, k, n))
                n += k

            def gather(job):
                win, jump, anchors, k, at = job
                out[at : at + k] = lut_sym[win[_expand(jump, anchors, k)]]

            list(ex.map(gather, jobs))
    if n < count:
        raise ValueError("truncated Huffman stream")
    return out
