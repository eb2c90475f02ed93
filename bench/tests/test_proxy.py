"""The codec proxies forward bytes unchanged and put back the originals."""

import numpy as np
import pytest

from bench import proxy


@pytest.fixture
def installed():
    originals = proxy.install()
    yield originals
    proxy.restore(originals)


def test_proxy_forwards_bytes_unchanged(installed):
    from repro.core import codecs
    from repro.core.selector import select

    x = np.cumsum(np.random.default_rng(0).standard_normal((48, 64)), axis=1).astype(np.float32)
    sel = select(x, eb_rel=1e-3)
    for name in ("sz", "zfp", "raw"):
        proxied, inner = codecs.get(name), installed[name]
        assert isinstance(proxied, proxy.TracedCodec)
        assert (proxied.blockwise, proxied.lossless, proxied.device_encode) == (
            inner.blockwise, inner.lossless, getattr(inner, "device_encode", False))
        data = proxied.encode(x, sel)
        assert data == inner.encode(x, sel)
        np.testing.assert_array_equal(proxied.decode(data), inner.decode(data))


def test_restore_puts_back_the_originals():
    from repro.core import codecs

    before = {n: codecs.get(n) for n in codecs.names()}
    proxy.restore(proxy.install())
    assert {n: codecs.get(n) for n in codecs.names()} == before
