"""Jit'd public wrappers over the Pallas kernels, with padding + dispatch.

`lorenzo_encode` / `lorenzo_decode` and `bot_fused` accept arbitrary-shape
fields; 2-D AND 3-D shapes route to the Pallas kernels (padded up to
multiples of the kernel's own tile — `lorenzo.tile` / `bot4.tile`, sized
from the field), everything else falls back to the core jnp paths.

Two decisions live here, once each:

* `interpret_mode` — whether the kernels are interpreted (CPU, the test
  and rehearsal platform) or compiled through Mosaic (TPU). Any other
  backend is an error: no kernel silently interprets on an accelerator.
* `pallas_rank` — which fields ride the kernel tier. The Lorenzo and BOT
  wrappers must agree on it, or a tiny field could encode on one path and
  be priced on another (DESIGN.md §3.3).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.transforms import lorenzo_forward, lorenzo_inverse

from . import bot4, lorenzo


def interpret_mode() -> bool:
    """True on CPU (kernels run in the Pallas interpreter), False on TPU
    (kernels compile); raises on any other backend."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels compile for TPU and interpret on CPU; "
        f"backend {backend!r} is neither"
    )


def pallas_rank(shape: tuple[int, ...]) -> int | None:
    """The Pallas kernel tier (2 or 3) serving `shape`, or None for the
    jnp reference path.

    THE shared dispatch predicate: `lorenzo_encode` once required
    `shape[0] >= 8` while `bot_fused` gated only on `ndim == 2`, so a
    (4, 40) field encoded on the reference path but priced on the kernel
    path. Every non-empty 2-D/3-D shape whose rows fit one Lorenzo tile
    (`lorenzo.MAX_ROW` values) rides the kernel tier — short dims pad up
    to the tile granularity and the zero padding is exact (Lorenzo's
    backward differences never look into trailing pad rows; BOT pad blocks
    are sliced off recon and bits) — so in-graph callers like
    `kvcomp.bot_compress_kv` always get real per-block bits. Keeping the
    predicate in one place makes the wrappers agree by construction
    (covered by tests/test_kernels3d.py::test_dispatch_predicate_shared).
    """
    nd = len(shape)
    if nd in (2, 3) and all(s > 0 for s in shape) and shape[-1] <= lorenzo.MAX_ROW:
        return nd
    return None


def _pad_to(x: jax.Array, block: tuple[int, ...]):
    pads = tuple((0, (-s) % b) for s, b in zip(x.shape, block))
    shape = x.shape
    if any(p for _, p in pads):
        x = jnp.pad(x, pads)
    return x, shape


def _crop(a: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    return a[tuple(slice(0, s) for s in shape)]


def lorenzo_encode(x: jax.Array, eb, block=None) -> jax.Array:
    """Quantize + n-D Lorenzo difference -> int32 codes (same shape).
    `block` overrides the launch tile (rows/planes only: tiles span the
    whole last dim)."""
    rank = pallas_rank(x.shape)
    if rank is None:
        delta = 2.0 * jnp.asarray(eb, jnp.float32)
        return lorenzo_forward(jnp.round(x.astype(jnp.float32) / delta)).astype(
            jnp.int32
        )
    blk = tuple(block or lorenzo.tile(x.shape))
    xp, shape = _pad_to(x, blk)
    enc = lorenzo.lorenzo2d_encode if rank == 2 else lorenzo.lorenzo3d_encode
    return _crop(enc(xp, eb, block=blk, interpret=interpret_mode()), shape)


def lorenzo_decode(d: jax.Array, eb, block=None) -> jax.Array:
    """Inverse Lorenzo (n-D cumsum) + dequantize -> f32 reconstruction."""
    k = lorenzo_inverse(d.astype(jnp.float32))
    if pallas_rank(d.shape) is None:
        return k * (2.0 * jnp.asarray(eb, jnp.float32))
    blk = tuple(block or lorenzo.tile(d.shape))
    kp, shape = _pad_to(k.astype(jnp.int32), blk)
    return _crop(lorenzo.dequantize(kp, eb, block=blk, interpret=interpret_mode()), shape)


def bot_fused(x: jax.Array, eb, transform: str = "zfp", block=None):
    """Fused ZFP-style transform/truncate -> (recon, bits-per-block)."""
    rank = pallas_rank(x.shape)
    if rank is None:
        # other ranks use the core jnp path
        from repro.core.zfp import zfp_stats

        st = zfp_stats(x, eb, transform=transform)
        return st.recon, None
    blk = tuple(block or bot4.tile(x.shape))
    xp, shape = _pad_to(x, blk)
    fused = bot4.bot2d_fused if rank == 2 else bot4.bot3d_fused
    recon, bits = fused(xp, eb, transform, block=blk, interpret=interpret_mode())
    return _crop(recon, shape), _crop(bits, tuple(-(-s // 4) for s in shape))
