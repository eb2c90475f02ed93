"""Codec correctness: error bounds, roundtrips, rate accounting (SZ & ZFP)."""

import numpy as np
import pytest

from repro.core import (
    select,
    select_and_compress,
    decompress,
    sz_compress,
    sz_decompress,
    sz_stats,
    zfp_compress,
    zfp_decompress,
    zfp_stats,
)
from repro.core import entropy as ent

import jax.numpy as jnp


def _tol(eb, x):
    # f32-output guarantee: eb plus a few output ulps (same as real SZ/ZFP)
    return eb + 4 * np.spacing(np.abs(x).max() + 1e-30)


def _field(shape, kind, seed):
    rng = np.random.default_rng(seed)
    if kind == "noise":
        return rng.standard_normal(shape).astype(np.float32)
    if kind == "smooth":
        grids = np.meshgrid(*[np.linspace(0, 4, s) for s in shape], indexing="ij")
        out = np.ones(shape)
        for g in grids:
            out = out * np.sin(g)
        return (out + 0.01 * rng.standard_normal(shape)).astype(np.float32)
    if kind == "walk":
        return np.cumsum(rng.standard_normal(shape), axis=-1).astype(np.float32)
    raise ValueError(kind)


SHAPES = [(2048,), (96, 80), (24, 40, 32)]
KINDS = ["noise", "smooth", "walk"]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("eb_rel", [1e-2, 1e-3, 1e-5])
def test_sz_error_bound_and_roundtrip(shape, kind, eb_rel):
    x = _field(shape, kind, 7)
    eb = eb_rel * (x.max() - x.min() + 1e-30)
    buf = sz_compress(x, eb)
    rec = sz_decompress(buf)
    assert rec.shape == x.shape and rec.dtype == np.float32
    assert np.abs(x - rec).max() <= _tol(eb, x)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("eb_rel", [1e-2, 1e-3, 1e-5])
def test_zfp_error_bound_and_roundtrip(shape, kind, eb_rel):
    x = _field(shape, kind, 11)
    eb = eb_rel * (x.max() - x.min() + 1e-30)
    buf = zfp_compress(x, eb)
    rec = zfp_decompress(buf)
    assert rec.shape == x.shape and rec.dtype == np.float32
    assert np.abs(x - rec).max() <= _tol(eb, x)


def _reference_planes(m, neg, nsb) -> list[int]:
    """Plane by plane, block by block: the plain definition of the ZFP
    plane-sectioned stream (refinement bits, k fields, significance bits,
    signs — each section over every block still coded at the plane)."""
    nblk, bsz = m.shape
    w = int(np.ceil(np.log2(bsz + 1)))
    out = []
    for p in range(int(nsb.max()) - 1, -1, -1):
        ref, ks, sig, signs = [], [], [], []
        for b in range(nblk):
            if nsb[b] <= p:
                continue
            rem = []
            for c in range(bsz):
                v = int(m[b, c])
                if v >> (p + 1):
                    ref.append((v >> p) & 1)
                else:
                    rem.append(c)
            if not rem:
                continue
            newly = [i for i, c in enumerate(rem) if (int(m[b, c]) >> p) & 1]
            k = newly[-1] + 1 if newly else 0
            ks.extend((k >> (w - 1 - j)) & 1 for j in range(w))
            sig.extend((int(m[b, rem[i]]) >> p) & 1 for i in range(k))
            signs.extend(int(neg[b, rem[i]]) for i in newly)
        out += ref + ks + sig + signs
    return out


@pytest.mark.parametrize("nd", [1, 2, 3])
def test_zfp_planes_match_reference_coder(nd):
    """The vectorized plane coder emits and reads exactly the stream of the
    plain loop — with blocks of every depth, all-zero blocks among them, so
    fewer blocks are coded at the top planes than at the bottom ones."""
    from repro.core import zfp

    rng = np.random.default_rng(nd)
    bsz, nblk = 4**nd, 60
    nsb = rng.integers(0, 12, nblk).astype(np.uint8)
    mag = rng.integers(0, 1 << 12, (nblk, bsz)) >> (12 - nsb.astype(np.int64))[:, None]
    mag[np.arange(nblk), rng.integers(0, bsz, nblk)] |= (1 << nsb.astype(np.int64)) >> 1
    neg = (rng.random((nblk, bsz)) < 0.5) & (mag > 0)
    bits = np.concatenate(zfp._emit_planes(mag, neg, nsb))
    assert bits.tolist() == _reference_planes(mag, neg, nsb)
    m, sgn, end = zfp._read_planes(bits, 0, nblk, bsz, nsb)
    assert end == len(bits)
    np.testing.assert_array_equal(m, mag)
    np.testing.assert_array_equal(sgn, neg)


def test_bounds_hold_fixed_seeds():
    """Deterministic twin of the hypothesis property test (which lives in
    test_property_codecs.py behind pytest.importorskip)."""
    for seed in (0, 17, 23):
        for kind in KINDS:
            x = _field((96, 80), kind, seed)
            eb = 1e-3 * (x.max() - x.min() + 1e-30)
            assert np.abs(x - sz_decompress(sz_compress(x, eb))).max() <= _tol(eb, x)
            assert np.abs(x - zfp_decompress(zfp_compress(x, eb))).max() <= _tol(eb, x)


def test_stats_match_actual_bytes_sz():
    """In-graph rate statistics track the byte codec within ~15%."""
    x = _field((256, 256), "smooth", 3)
    eb = 1e-3 * (x.max() - x.min())
    st_ = sz_stats(jnp.asarray(x), eb)
    actual = 8 * len(sz_compress(x, eb)) / x.size
    assert abs(float(st_.bitrate) - actual) / actual < 0.25
    # reconstruction identical up to dequantize dtype handling
    assert np.abs(np.asarray(st_.recon) - sz_decompress(sz_compress(x, eb))).max() < 2e-5 * (
        np.abs(x).max()
    )


def test_stats_match_actual_bytes_zfp():
    x = _field((256, 256), "smooth", 3)
    eb = 1e-3 * (x.max() - x.min())
    st_ = zfp_stats(jnp.asarray(x), eb)
    actual = 8 * len(zfp_compress(x, eb)) / x.size
    assert abs(float(st_.bitrate) - actual) / actual < 0.1
    # the stats path runs in f32, the byte codec in f64 — truncation-boundary
    # jitter can move single coefficients one step; both stay within the bound
    rec = zfp_decompress(zfp_compress(x, eb))
    np.testing.assert_allclose(np.asarray(st_.recon), rec, atol=2 * eb)


def test_zfp_overpreserves_vs_sz():
    """§6.4: at the same eb, ZFP's actual error is well below the bound."""
    x = _field((128, 128), "smooth", 5)
    eb = 1e-3 * (x.max() - x.min())
    err_sz = np.abs(x - sz_decompress(sz_compress(x, eb))).max()
    err_zfp = np.abs(x - zfp_decompress(zfp_compress(x, eb))).max()
    assert err_zfp < err_sz  # over-preservation

    st_sz = sz_stats(jnp.asarray(x), eb)
    st_zfp = zfp_stats(jnp.asarray(x), eb)
    assert float(st_zfp.psnr) > float(st_sz.psnr)


def test_huffman_roundtrip():
    rng = np.random.default_rng(0)
    syms = rng.geometric(0.05, size=20000).clip(0, 400).astype(np.int64)
    freqs = np.bincount(syms, minlength=401)
    table = ent.build_table(freqs)
    buf = ent.encode(syms, table)
    table2 = ent.HuffmanTable.from_bytes(table.to_bytes())
    out = ent.decode(buf, table2, len(syms))
    np.testing.assert_array_equal(out, syms)
    # rate is within 10% of entropy + table
    h = ent.entropy_bits(freqs)
    assert 8 * len(buf) / len(syms) <= h * 1.1 + 1.0


def _reference_encode(syms, table) -> bytes:
    """Code by code, bit by bit: the plain definition of the stream."""
    bits = []
    for s in syms:
        ln, code = int(table.lens[s]), int(table.codes[s])
        bits.extend((code >> (ln - 1 - i)) & 1 for i in range(ln))
    return np.packbits(np.asarray(bits, np.uint8)).tobytes() if bits else b""


def _reference_decode(buf, table, count) -> np.ndarray:
    """Prefix match one code at a time."""
    by_code = {
        (int(table.lens[s]), int(table.codes[s])): s for s in np.nonzero(table.lens)[0]
    }
    bits = np.unpackbits(np.frombuffer(buf, np.uint8))
    out, pos = [], 0
    while len(out) < count:
        code, ln = 0, 0
        while (ln, code) not in by_code:
            code, ln, pos = (code << 1) | int(bits[pos]), ln + 1, pos + 1
        out.append(by_code[(ln, code)])
    return np.asarray(out, np.int64)


@pytest.mark.parametrize(
    "n, scale, chunk",
    [(1, 1.0, None), (5000, 3.0, None), (20000, 300.0, None), (20000, 30.0, 1000)],
)
def test_huffman_matches_reference_coder(monkeypatch, n, scale, chunk):
    """The vectorized, chunked coder emits and reads exactly the stream of
    the code-by-code definition — including across chunk boundaries."""
    if chunk is not None:
        monkeypatch.setattr(ent, "_ENCODE_CHUNK", chunk)
        monkeypatch.setattr(ent, "_DECODE_CHUNK", 8 * chunk)
    rng = np.random.default_rng(n)
    syms = np.clip(np.round(rng.standard_normal(n) * scale) + 2000, 0, 4000).astype(np.int64)
    table = ent.build_table(np.bincount(syms, minlength=4001))
    buf = ent.encode(syms, table)
    assert buf == _reference_encode(syms, table)
    np.testing.assert_array_equal(ent.decode(buf, table, n), _reference_decode(buf, table, n))
    np.testing.assert_array_equal(ent.decode(buf, table, n), syms)


def test_huffman_degenerate_single_symbol():
    syms = np.zeros(100, dtype=np.int64)
    table = ent.build_table(np.bincount(syms, minlength=3))
    buf = ent.encode(syms, table)
    out = ent.decode(buf, ent.HuffmanTable.from_bytes(table.to_bytes()), 100)
    np.testing.assert_array_equal(out, syms)


@pytest.mark.parametrize("kind", KINDS)
def test_select_and_compress_roundtrip(kind):
    x = _field((128, 96), kind, 9)
    cf = select_and_compress(x, eb_rel=1e-3)
    rec = decompress(cf)
    vr = x.max() - x.min()
    assert np.abs(x - rec).max() <= _tol(1e-3 * vr, x)
    assert cf.codec in ("sz", "zfp", "raw")


def test_select_constant_field_is_raw_or_tiny():
    x = np.full((64, 64), 3.14, dtype=np.float32)
    cf = select_and_compress(x, eb_rel=1e-3)
    rec = decompress(cf)
    np.testing.assert_allclose(rec, x, atol=1e-6)


def test_select_tiny_field_raw():
    x = np.arange(10, dtype=np.float32)
    cf = select_and_compress(x, eb_rel=1e-3)
    assert cf.codec == "raw"
    np.testing.assert_array_equal(decompress(cf), x)
