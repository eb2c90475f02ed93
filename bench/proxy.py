"""Host spans around the program's codecs, through its public registry.

`install()` registers, for each codec the program has built in, a proxy
under the same name (`core.codecs.register(..., replace=True)`): it
forwards `encode`, `encode_device` and `decode` unchanged and wraps each
call in `TraceAnnotation("host_encode.<codec>")` or
`"host_decode.<codec>"`. Only traced runs install it.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation


class TracedCodec:
    def __init__(self, inner):
        self.inner = inner
        self.name = inner.name
        self.blockwise = inner.blockwise
        self.pointwise_bound = inner.pointwise_bound
        self.lossless = inner.lossless
        self._enc = f"host_encode.{inner.name}"
        self._dec = f"host_decode.{inner.name}"

    @property
    def device_encode(self) -> bool:
        return bool(getattr(self.inner, "device_encode", False))

    def encode(self, view32, selection) -> bytes:
        with TraceAnnotation(self._enc):
            return self.inner.encode(view32, selection)

    def encode_device(self, view32, selection):
        with TraceAnnotation(self._enc):
            return self.inner.encode_device(view32, selection)

    def decode(self, data: bytes):
        with TraceAnnotation(self._dec):
            return self.inner.decode(data)


def install() -> dict:
    """Proxy every registered codec; returns the originals for `restore`."""
    from repro.core import codecs

    originals = {n: codecs.get(n) for n in codecs.names()}
    for codec in originals.values():
        codecs.register(TracedCodec(codec), replace=True)
    return originals


def restore(originals: dict) -> None:
    from repro.core import codecs

    for codec in originals.values():
        codecs.register(codec, replace=True)
