"""Pallas TPU kernels: fused prequantize + 2-D/3-D integer-Lorenzo encode.

The SZ Stage I+II hot spot (DESIGN.md §3.1, §3.3, §3.4). One pass over
HBM: round(x / 2eb) and the n-D Lorenzo difference of the integer codes,
tiled through VMEM. The residual is one backward difference per axis of
the integer codes, so a tile needs exactly one neighbour per axis:

  * x (lanes): every tile spans the field's whole last dim, so the left
    neighbour is in the tile — one lane rotate, with lane 0 (the domain
    boundary) masked to zero;
  * y (sublanes): rows are tiled in multiples of 8; the row above a tile
    is the last row of the aligned 8-row block before it, fetched as its
    own block with the block index clamped at 0 and masked on the first
    row tile;
  * z (3-D, leading dim): z is the innermost, sequential grid axis and the
    last plane of the previous tile is carried in VMEM scratch; the one
    row above the tile in that plane arrives as an aligned (1, 8, n) block.

Every block is (8, 128)-aligned or spans its whole array dim, and no
index map ever points before the array.

TPU mapping notes:
  * the tile holds ~`TILE_BYTES` of f32: rows (and, once a tile holds
    whole planes, planes) are sized from the row width;
  * round / sub are VPU element ops and the shifts are XLU rotates; the
    whole kernel is memory-bound, so fusing quantize+stencil halves HBM
    traffic vs running them separately.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: f32 bytes of one input tile: double-buffered input and output plus a
#: few tile-sized temporaries stay well inside v5e's 16 MiB scoped VMEM
TILE_BYTES = 1 << 20
#: widest row a tile can hold: one 8-row tile of f32 at `TILE_BYTES`
MAX_ROW = TILE_BYTES // (4 * 8)

_SMEM_SCALAR = pl.BlockSpec(memory_space=pltpu.SMEM)


def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b


def tile(shape: tuple[int, ...]) -> tuple[int, ...]:
    """The (bm, n) / (bz, bm, n) launch tile for a field of `shape`: the
    whole last dim, rows in multiples of 8 split evenly so padding stays
    under 8 rows per tile, and in 3-D as many planes as fit once a tile
    holds whole planes."""
    n = int(shape[-1])
    m = int(shape[-2])
    cap = max(8, (TILE_BYTES // (4 * n)) // 8 * 8)
    bm = _round_up(-(-m // -(-m // cap)), 8)
    if len(shape) == 2:
        return (bm, n)
    z = int(shape[0])
    bz = max(1, TILE_BYTES // (4 * bm * n)) if bm >= m else 1
    bz = -(-z // -(-z // bz))
    return (bz, bm, n)


def _prev(a: jax.Array, axis: int, fill) -> jax.Array:
    """`a` one element back along `axis` (a[i-1] at i), with `fill` at
    element 0."""
    rolled = pltpu.roll(a, 1, axis)
    first = jax.lax.broadcasted_iota(jnp.int32, a.shape, axis) == 0
    return jnp.where(first, fill, rolled)


def _prev_planes(a: jax.Array, first: jax.Array) -> jax.Array:
    """`a` one plane back along the leading dim, with `first` in plane 0."""
    if a.shape[0] == 1:
        return first
    return jnp.concatenate([first, a[:-1]], axis=0)


def _encode2d_kernel(eb_ref, x_ref, up_ref, out_ref):
    i = pl.program_id(0)
    delta = 2.0 * eb_ref[0]
    k = jnp.round(x_ref[...] / delta)  # (bm, n)
    up = jnp.where(i > 0, jnp.round(up_ref[7:8, :] / delta), 0.0)  # (1, n)
    ky = k - _prev(k, 0, up)
    out_ref[...] = (ky - _prev(ky, 1, 0.0)).astype(jnp.int32)


def _check(shape, block):
    assert block[-1] == shape[-1], ("tiles span the whole last dim", shape, block)
    assert block[-2] % 8 == 0, ("rows tile in multiples of 8", block)
    assert all(s % b == 0 for s, b in zip(shape, block)), (shape, block)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def lorenzo2d_encode(
    x: jax.Array, eb: jax.Array | float, *, block: tuple[int, int], interpret: bool
) -> jax.Array:
    """Fused quantize+Lorenzo for a 2-D f32 field -> int32 residual codes.

    Requires shape divisible by `block` (ops.py pads)."""
    m, n = x.shape
    _check(x.shape, block)
    bm = block[0]
    r8 = bm // 8
    eb_arr = jnp.full((1,), eb, jnp.float32)
    return pl.pallas_call(
        _encode2d_kernel,
        grid=(m // bm,),
        in_specs=[
            _SMEM_SCALAR,
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            # the aligned 8-row block ending at row i*bm - 1
            pl.BlockSpec((8, n), lambda i: (jnp.maximum(i * r8 - 1, 0), 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(eb_arr, x, x)


def _encode3d_kernel(eb_ref, x_ref, up_ref, zup_ref, out_ref, zprev_ref):
    """3-D extension of `_encode2d_kernel` (DESIGN.md §3.4): a backward
    difference along z (the previous plane is carried in `zprev_ref`),
    then y (the row above the tile, itself z-differenced), then x."""
    j = pl.program_id(0)
    i = pl.program_id(1)
    delta = 2.0 * eb_ref[0]
    k = jnp.round(x_ref[...] / delta)  # (bz, bm, n)
    zprev = jnp.where(i > 0, zprev_ref[...], 0.0)  # (1, bm, n)
    zprev_ref[...] = k[-1:]
    kz = k - _prev_planes(k, zprev)
    up = jnp.where(j > 0, jnp.round(up_ref[:, 7:8, :] / delta), 0.0)  # (bz, 1, n)
    zup = jnp.where((i > 0) & (j > 0), jnp.round(zup_ref[:, 7:8, :] / delta), 0.0)
    upz = up - _prev_planes(up, zup)
    ky = kz - _prev(kz, 1, upz)
    out_ref[...] = (ky - _prev(ky, 2, 0.0)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def lorenzo3d_encode(
    x: jax.Array,
    eb: jax.Array | float,
    *,
    block: tuple[int, int, int],
    interpret: bool,
) -> jax.Array:
    """Fused quantize+Lorenzo for a 3-D f32 field -> int32 residual codes.

    Requires shape divisible by `block` (ops.py pads)."""
    z, m, n = x.shape
    _check(x.shape, block)
    bz, bm, _ = block
    r8 = bm // 8
    eb_arr = jnp.full((1,), eb, jnp.float32)
    # grid (row tile j, z tile i): z innermost, so the carried plane is the
    # one just above in z
    return pl.pallas_call(
        _encode3d_kernel,
        grid=(m // bm, z // bz),
        in_specs=[
            _SMEM_SCALAR,
            pl.BlockSpec((bz, bm, n), lambda j, i: (i, j, 0)),
            pl.BlockSpec((bz, 8, n), lambda j, i: (i, jnp.maximum(j * r8 - 1, 0), 0)),
            pl.BlockSpec(
                (1, 8, n),
                lambda j, i: (jnp.maximum(i * bz - 1, 0), jnp.maximum(j * r8 - 1, 0), 0),
            ),
        ],
        out_specs=pl.BlockSpec((bz, bm, n), lambda j, i: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((z, m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, bm, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(eb_arr, x, x, x)


def _dequant_kernel(eb_ref, k_ref, out_ref):
    delta = 2.0 * eb_ref[0]
    out_ref[...] = k_ref[...].astype(jnp.float32) * delta


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dequantize(
    k: jax.Array, eb: jax.Array | float, *, block: tuple[int, ...], interpret: bool
) -> jax.Array:
    """Reconstruction from integer codes (decode-side Stage II inverse) for
    a 2-D or 3-D field, on the encoder's tiles.

    The Lorenzo inverse itself (an n-D cumsum) is left to XLA's optimized
    scan; this kernel fuses only the elementwise dequantize."""
    _check(k.shape, block)
    grid = tuple(s // b for s, b in zip(k.shape, block))
    index = lambda *ij: ij  # noqa: E731 - block index == grid index
    eb_arr = jnp.full((1,), eb, jnp.float32)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[_SMEM_SCALAR, pl.BlockSpec(block, index)],
        out_specs=pl.BlockSpec(block, index),
        out_shape=jax.ShapeDtypeStruct(k.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid)
        ),
        interpret=interpret,
    )(eb_arr, k)
