"""Device-resident Stage III (DESIGN.md §3.7): packer parity + fallbacks.

The load-bearing contract: fed the SAME quantized codes, the in-graph
packer and the host Stage III produce BYTE-IDENTICAL streams — so every
device-packed container decodes through the unchanged host decoders. The
parity surfaces (`sz_device_residuals`, `zfp_device_codes`) exist exactly
so these tests (and the `device_encode_parity` bench gate) can feed the
host encoder the device's codes and compare bytes, independent of the
f32-vs-f64 quantization boundary noted in the module docstring.
"""

import jax
import numpy as np
import pytest

from repro.core import api, codecs, device_encode as de, selector, sz, zfp
from repro.core.policy import Policy
from repro.runtime import kvcomp


def _tol(eb, x):
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


SHAPES = [(2048,), (96, 80), (24, 40, 32), (30, 29)]  # incl. ragged
KINDS = ["smooth", "walk"]


# ---------------------------------------------------------------------------
# byte parity on the same codes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("kind", KINDS)
def test_sz_device_stream_byte_parity(shape, kind):
    x = _field(shape, kind, 3)
    eb = 1e-3 * float(x.max() - x.min())
    dev = de.sz_encode_device(x, eb)
    assert dev is not None
    # the host Stage III over the device's own residuals
    d = de.sz_device_residuals(x, eb)
    delta = float(np.float32(2.0) * np.float32(eb))
    host = sz.sz_encode_residuals(d, x.shape, delta, magic=sz.DEVICE_MAGIC)
    assert dev == host


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("kind", KINDS)
def test_zfp_device_stream_byte_parity(shape, kind):
    x = _field(shape, kind, 5)
    eb = 1e-3 * float(x.max() - x.min())
    dev = de.zfp_encode_device(x, eb)
    assert dev is not None
    q, e = de.zfp_device_codes(x, eb)
    padded = tuple(s + (-s) % 4 for s in x.shape)
    host = zfp.zfp_encode_quantized(q, e, x.shape, padded, eb)
    assert dev == host


def test_sz_parity_escape_heavy():
    """Outliers past RESIDUAL_RADIUS exercise the escape-literal scatter."""
    rng = np.random.default_rng(11)
    x = np.cumsum(rng.standard_normal((64, 64)), axis=0).astype(np.float32)
    x[::7, ::5] += 1e4 * rng.standard_normal(x[::7, ::5].shape).astype(np.float32)
    eb = 1e-6 * float(x.max() - x.min())
    dev = de.sz_encode_device(x, eb)
    assert dev is not None
    d = de.sz_device_residuals(x, eb)
    assert np.sum(np.abs(d) > sz.RESIDUAL_RADIUS) > 0  # escapes really fired
    delta = float(np.float32(2.0) * np.float32(eb))
    assert dev == sz.sz_encode_residuals(d, x.shape, delta, magic=sz.DEVICE_MAGIC)


def test_constant_field_parity():
    """All-zero symbols / zero bit-planes — the degenerate stream shapes."""
    x = np.full((32, 32), 3.25, np.float32)
    dev = de.sz_encode_device(x, 1e-3)
    d = de.sz_device_residuals(x, 1e-3)
    delta = float(np.float32(2.0) * np.float32(1e-3))
    assert dev == sz.sz_encode_residuals(d, x.shape, delta, magic=sz.DEVICE_MAGIC)
    devz = de.zfp_encode_device(x, 1e-3)
    q, e = de.zfp_device_codes(x, 1e-3)
    assert devz == zfp.zfp_encode_quantized(q, e, x.shape, x.shape, 1e-3)


# ---------------------------------------------------------------------------
# host decoders consume device streams; bound holds
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", SHAPES)
def test_device_streams_decode_within_bound(shape):
    x = _field(shape, "walk", 9)
    eb = 1e-3 * float(x.max() - x.min())
    rec_sz = sz.sz_decompress(de.sz_encode_device(x, eb)).reshape(x.shape)
    assert np.abs(rec_sz - x).max() <= _tol(eb, x)
    rec_zfp = zfp.zfp_decompress(de.zfp_encode_device(x, eb)).reshape(x.shape)
    assert np.abs(rec_zfp - x).max() <= _tol(eb, x)


def test_sz_device_magic_roundtrips():
    x = _field((64, 64), "smooth", 2)
    buf = de.sz_encode_device(x, 1e-3)
    assert buf[:4] == sz.DEVICE_MAGIC
    # host streams keep the SZJ1 magic; the decoder accepts both
    assert sz.sz_compress(x, 1e-3)[:4] != sz.DEVICE_MAGIC
    sz.sz_decompress(buf)


# ---------------------------------------------------------------------------
# fallback rules: None means host coder, never a truncated stream
# ---------------------------------------------------------------------------


def test_zero_size_and_bad_bounds_fall_back():
    empty = np.zeros((0,), np.float32)
    assert de.sz_encode_device(empty, 1e-3) is None
    assert de.zfp_encode_device(empty, 1e-3) is None
    x = _field((16, 16), "walk", 1)
    assert de.sz_encode_device(x, 0.0) is None
    assert de.zfp_encode_device(x, 0.0) is None
    assert de.zfp_encode_device(x, float("nan")) is None


def test_code_magnitude_guard_falls_back():
    """Bound so tight the codes leave f32-exact integer range -> None."""
    x = (1e6 * _field((32, 32), "walk", 4)).astype(np.float32)
    assert de.sz_encode_device(x, 1e-4) is None
    assert de.zfp_encode_device(x, 1e-6) is None


def test_arena_overflow_guard_falls_back(monkeypatch):
    """A rate-model under-estimate must surface as a clean None (the pack
    arena DROPS out-of-range bits, and the emitter's true bit total is
    checked against capacity) — never as a truncated container."""
    monkeypatch.setattr(de.pack, "arena_words", lambda bits, min_words=1: 1)
    x = _field((64, 64), "walk", 8)
    assert de.zfp_encode_device(x, 1e-3 * float(x.max() - x.min())) is None


def test_encode_with_selection_falls_back_to_host(monkeypatch):
    """Through the registry path: a declining device tier means the host
    coder runs and the field still encodes + decodes normally."""
    monkeypatch.setattr(de.pack, "arena_words", lambda bits, min_words=1: 1)
    x = _field((64, 64), "walk", 8)
    cf = selector.encode_with_selection(
        x, selector.select(x, eb_rel=1e-3), device_encode=True
    )
    rec = api.decompress(cf).reshape(x.shape)
    eb = 1e-3 * float(x.max() - x.min())
    assert np.abs(rec - x).max() <= _tol(eb, x)


# ---------------------------------------------------------------------------
# integration: registry capability, api flag, kv page codec
# ---------------------------------------------------------------------------


def test_registry_capability_flags():
    assert codecs.supports_device_encode("sz")
    assert codecs.supports_device_encode("zfp")
    assert not codecs.supports_device_encode("raw")
    # pre-flag third-party codecs keep satisfying the protocol
    class Legacy:
        name, blockwise, pointwise_bound, lossless = "legacy", False, True, False

        def encode(self, v, s):
            return v.tobytes()

        def decode(self, b):
            return codecs.writeable_frombuffer(b, np.float32)

    assert not getattr(Legacy(), "device_encode", False)


@pytest.mark.parametrize("sharded", [False, True])
def test_compress_pytree_device_encode_roundtrip(sharded):
    rng = np.random.default_rng(6)
    tree = {
        "walk": np.cumsum(rng.standard_normal((64, 64)), 0).astype(np.float32),
        "noise": rng.standard_normal((512,)).astype(np.float32),
        "small": np.arange(3, dtype=np.float32),
    }
    ct = api.compress_pytree(
        tree, policy=Policy.fixed_accuracy(eb_rel=1e-3),
        sharded=sharded, device_encode=True,
    )
    back = api.decompress_pytree(ct)
    for k, v in tree.items():
        vr = float(v.max() - v.min()) if v.size else 0.0
        assert np.abs(back[k] - v).max() <= _tol(1e-3 * vr, v)


def test_kv_page_device_encode_roundtrip():
    rng = np.random.default_rng(7)
    page = np.cumsum(rng.standard_normal((64, 256)), axis=0).astype(np.float32)
    cp = kvcomp.compress_page(
        page, Policy.fixed_accuracy(eb_rel=1e-2), device_encode=True
    )
    assert cp.codec == "zfp"
    assert cp.nbytes == len(cp.payload) < page.nbytes  # literal footprint
    rec = kvcomp.decompress_page(cp)
    assert rec.shape == page.shape and rec.dtype == page.dtype
    vr = float(page.max() - page.min())
    assert np.abs(rec - page).max() <= _tol(1e-2 * vr, page)
    # raw policy is untouched by the flag: exact bytes either way
    raw = kvcomp.compress_page(page, Policy.raw(), device_encode=True)
    assert raw.codec == "raw"
    assert np.array_equal(kvcomp.decompress_page(raw), page)


# ---------------------------------------------------------------------------
# where Stage III runs: `selector.encode_tier`
# ---------------------------------------------------------------------------


class _StubCodec:
    """Records which of its encoders ran; `declines` makes the device
    encoder hand the field back (None), as the §3.7 fallback rules do."""

    name, blockwise, pointwise_bound, lossless = "stub", False, True, False
    device_encode = True

    def __init__(self, declines: bool):
        self.declines = declines
        self.calls: list[str] = []

    def encode(self, view, sel):
        self.calls.append("host")
        return b"host"

    def encode_device(self, view, sel):
        self.calls.append("device")
        return None if self.declines else b"device"

    def decode(self, data):
        raise NotImplementedError


N_MIN = selector.DEVICE_ENCODE_MIN_VALUES


@pytest.mark.parametrize("backend,flag,n,declines,want", [
    ("cpu", None, N_MIN, False, ["host"]),
    ("cpu", True, 4096, False, ["device"]),
    ("tpu", None, N_MIN, False, ["device"]),
    ("tpu", None, N_MIN - 1, False, ["host"]),
    ("tpu", False, N_MIN, False, ["host"]),
    ("tpu", None, N_MIN, True, ["device", "host"]),
])
def test_encode_tier_decides_from_backend_and_size(monkeypatch, backend, flag, n, declines,
                                                   want):
    """`encode_with_selection` and `encode_view_segment` share one decision:
    the device tier on a TPU backend at or above the size threshold, the
    host coder below it, on CPU, or where the flag forces it; a decline
    runs the host coder under `repro.fallback.device_declined`."""
    from repro.core import sharded

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    stub = _StubCodec(declines)
    monkeypatch.setitem(codecs._REGISTRY, "stub", stub)
    spans = []

    class Recorder(selector.TraceAnnotation):
        def __init__(self, name, **kwargs):
            spans.append(name)
            super().__init__(name, **kwargs)

    monkeypatch.setattr(selector, "TraceAnnotation", Recorder)
    sel = selector.Selection("stub", 1e-3, 1e-3, 8.0, 9.0, 60.0, 1.0, 0.05)
    x = np.zeros((n,), np.float32)
    assert selector.encode_tier("stub", n, flag) == want[0]
    cf = selector.encode_with_selection(x, sel, device_encode=flag)
    assert (cf.codec, cf.data) == ("stub", want[-1].encode())
    assert sharded.encode_view_segment(x, sel, device_encode=flag) == ("stub", cf.data)
    assert stub.calls == want * 2
    assert spans == ["repro.fallback.device_declined"] * 2 * declines
    if backend == "cpu" and flag is None:
        # the real codecs: the default streams are the host coders' bytes
        rng = np.random.default_rng(9)
        tree = {"walk": np.cumsum(rng.standard_normal((64, 64)), 0).astype(np.float32),
                "waves": np.sin(np.linspace(0, 300, 4096)).astype(np.float32).reshape(64, 64)}
        pol = Policy.fixed_accuracy(eb_rel=1e-4)
        ct = api.compress_pytree(tree, pol)
        host = api.compress_pytree(tree, pol, device_encode=False)
        assert {k: (f.codec, f.data) for k, f in ct.fields.items()} == {
            k: (f.codec, f.data) for k, f in host.fields.items()}
