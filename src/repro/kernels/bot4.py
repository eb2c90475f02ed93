"""Pallas TPU kernels: fused ZFP Stage I+II surrogate for 2-D/3-D fields.

Per VMEM tile: 4x4 (or 4x4x4) blocking -> exponent alignment -> block
orthogonal transform T(t) (paper §4.2) -> bit-plane truncation ->
(reconstruction, bits-per-block). This is the in-graph hot spot for
KV-cache / activation compression and for accelerating `zfp_stats`.

TPU mapping notes (DESIGN.md §3.2, §3.5):
  * the tile keeps the field's own layout — no in-kernel block reshape.
    Along the sublane and lane axes, an aligned group of 4 is one block
    edge: the block max is a two-step butterfly of rotates, and T(t) is
    applied as out[4g+a] = sum_b T[a,b] x[4g+b], each x[4g+b] gathered by
    rotates and an iota-selected shift (VPU + XLU work, in the same
    operation order as `ref.py`). In 3-D the tile holds exactly one block
    of planes (bz = 4), so the z axis is plain plane arithmetic;
  * exponent alignment uses exp2/log2 on the VPU instead of integer
    exponent plumbing (no bit-twiddling datapath on TPU vector lanes);
  * the bits output uses the closed-form `block_bits` model (the exact
    plane-sectioned count needs a 31-iteration plane loop — measured as
    not worth the VPU time in-kernel; ops.py exposes the exact host
    count). Each element carries its share of its block's bits, and two
    small 0/1 matmuls on the MXU sum the shares per block — the
    compaction to one value per block that a lane-strided load cannot do;
  * tiles are at most 128 (2-D) / 64 (3-D) rows by 512 lanes, so the
    (rows/4, lanes/4) bits block is (8, 128)-aligned whenever the grid
    has more than one tile along that axis, and spans the whole bits
    array otherwise.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.transforms import bot_linf_gain, bot_matrix

BLOCK_HEADER_BITS = 24  # must match repro.core.embedded
#: tile caps: (rows, lanes) in 2-D; 3-D tiles are (4, rows, lanes)
MAX_ROWS = {2: 128, 3: 64}
MAX_LANES = 512

_SMEM_SCALAR = pl.BlockSpec(memory_space=pltpu.SMEM)
_HIGHEST = jax.lax.Precision.HIGHEST


def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b


def tile(shape: tuple[int, ...]) -> tuple[int, ...]:
    """The launch tile for a field of `shape` (ops.py pads to it): rows
    rounded to 8 sublanes and lanes to 128, each capped, plus bz = 4 in
    3-D."""
    nd = len(shape)
    rows = min(_round_up(shape[-2], 8), MAX_ROWS[nd])
    lanes = min(_round_up(shape[-1], 128), MAX_LANES)
    return (4, rows, lanes) if nd == 3 else (rows, lanes)


def _check(shape, block):
    assert all(s % b == 0 for s, b in zip(shape, block)), (shape, block)
    if len(block) == 3:
        assert block[0] == 4, ("3-D tiles hold one block of planes", block)
    bm, bn = block[-2:]
    assert bm % 8 == 0 and bn % 128 == 0, ("(8, 128)-aligned tiles", block)
    # the bits block (bm/4, bn/4) is (8, 128)-aligned or the whole dim
    assert (bm // 4) % 8 == 0 or bm == shape[-2], block
    assert (bn // 4) % 128 == 0 or bn == shape[-1], block


def _roll(x, shift: int, axis: int):
    """jnp.roll semantics (x[i - shift] lands at i) on a kernel value."""
    return pltpu.roll(x, shift % x.shape[axis], axis)


def _select(pos, vals):
    """vals[pos] elementwise, pos in 0..3."""
    out = vals[3]
    for a in (2, 1, 0):
        out = jnp.where(pos == a, vals[a], out)
    return out


def _group_pos(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis) & 3


def _block_max(x, axes):
    """Max over each aligned 4-group along every axis in `axes`, broadcast
    back to the group (butterfly: partners pos^1, then pos^2)."""
    for ax in axes:
        pos = _group_pos(x.shape, ax)
        for d in (1, 2):
            ahead = (pos & d) == 0
            x = jnp.maximum(x, jnp.where(ahead, _roll(x, -d, ax), _roll(x, d, ax)))
    return x


def _transform(x, T, axis):
    """out[4g+a] = sum_b T[a][b] * x[4g+b] along `axis` (sequential in b,
    as in `ref._transform_axis`)."""
    pos = _group_pos(x.shape, axis)
    shifted = {s: x if s == 0 else _roll(x, s, axis) for s in range(-3, 4)}
    acc = None
    for b in range(4):
        xb = _select(pos, [shifted[a - b] for a in range(4)])
        term = _select(pos, [float(T[a][b]) for a in range(4)]) * xb
        acc = term if acc is None else acc + term
    return acc


def _plane_transform(planes, T):
    """The z-axis transform over one block of 4 planes."""
    return [
        T[a][0] * planes[0] + T[a][1] * planes[1] + T[a][2] * planes[2]
        + T[a][3] * planes[3]
        for a in range(4)
    ]


def _block_sums(per, rows: int, lanes: int):
    """(rows/4, lanes/4) per-block sums of `per` (rows, lanes), as two 0/1
    matmuls (exact: the shares are small dyadic values)."""
    cr = (
        jax.lax.broadcasted_iota(jnp.int32, (rows // 4, rows), 1) >> 2
        == jax.lax.broadcasted_iota(jnp.int32, (rows // 4, rows), 0)
    ).astype(jnp.float32)
    cc = (
        jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes // 4), 0) >> 2
        == jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes // 4), 1)
    ).astype(jnp.float32)
    s = jnp.dot(cr, per, precision=_HIGHEST, preferred_element_type=jnp.float32)
    return jnp.dot(s, cc, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _truncate(c, e, eb, gain):
    """Conservative power-of-two bit-plane cutoff (over-preservation,
    §6.4) -> (nsb, midpoint reconstruction of the coefficients)."""
    raw = eb / (jnp.exp2(e) * gain)
    step = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(raw, 2.0**-60))))
    q = jnp.abs(c) / step
    m = jnp.trunc(q)
    nsb = jnp.where(m >= 1.0, jnp.floor(jnp.log2(jnp.maximum(m, 1.0))) + 1.0, 0.0)
    rc = jnp.sign(c) * jnp.where(m > 0, (m + 0.5) * step, 0.0)
    return nsb, rc


def _share(nsb, maxp, w: int, nvals: int):
    """Each coefficient's share of its block's rate model (header aside):
    sum over the block = w*maxplane + sum nsb + 2*nsig."""
    return nsb + 2.0 * (nsb > 0.0).astype(jnp.float32) + (w / nvals) * maxp


def _bot2d_kernel(eb_ref, x_ref, recon_ref, bits_ref, *, T, gain2):
    bm, bn = x_ref.shape
    Tt = [list(r) for r in zip(*T)]
    eb = eb_ref[0]
    x = x_ref[...]
    mx = jnp.maximum(_block_max(jnp.abs(x), (0, 1)), 1e-30)
    e = jnp.ceil(jnp.log2(mx))
    scale = jnp.exp2(-e)
    c = _transform(_transform(x * scale, T, 0), T, 1)
    nsb, rc = _truncate(c, e, eb, gain2)
    # rate model (see module docstring): header + w*maxplane + sum nsb + 2*nsig
    w = math.ceil(math.log2(16 + 1))
    per = _share(nsb, _block_max(nsb, (0, 1)), w, 16)
    bits_ref[...] = BLOCK_HEADER_BITS + _block_sums(per, bm, bn)
    # inverse transform + de-normalization
    recon_ref[...] = _transform(_transform(rc, Tt, 0), Tt, 1) / scale


def _launch(kernel, x, eb, block, interpret):
    """Shared pallas_call of the 2-D/3-D kernels: one tile in, its
    reconstruction and bits block out."""
    _check(x.shape, block)
    grid = tuple(s // b for s, b in zip(x.shape, block))
    bits_block = tuple(b // 4 for b in block)  # (1, ., .) in 3-D: bz == 4
    index = lambda *ij: ij  # noqa: E731 - block index == grid index
    eb_arr = jnp.full((1,), eb, jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[_SMEM_SCALAR, pl.BlockSpec(block, index)],
        out_specs=[pl.BlockSpec(block, index), pl.BlockSpec(bits_block, index)],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, jnp.float32),
            jax.ShapeDtypeStruct(tuple(s // 4 for s in x.shape), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid)
        ),
        interpret=interpret,
    )(eb_arr, x)


def _matrix(transform: str) -> list[list[float]]:
    return np.asarray(bot_matrix(transform), np.float32).tolist()


@functools.partial(jax.jit, static_argnames=("transform", "block", "interpret"))
def bot2d_fused(
    x: jax.Array,
    eb: jax.Array | float,
    transform: str = "zfp",
    *,
    block: tuple[int, int],
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Fused ZFP-style transform+truncate for a 2-D f32 field.

    Returns (reconstruction (m, n) f32, bits (m/4, n/4) f32).
    Requires shape divisible by `block` (ops.py pads).
    """
    gain2 = float(bot_linf_gain(transform) ** 2)
    kernel = functools.partial(_bot2d_kernel, T=_matrix(transform), gain2=gain2)
    return _launch(kernel, x, eb, block, interpret)


def _bot3d_kernel(eb_ref, x_ref, recon_ref, bits_ref, *, T, gain3):
    """4x4x4 generalization of `_bot2d_kernel` (DESIGN.md §3.5): the tile
    is one block of 4 planes; T(t) runs along z (plane arithmetic), then y
    and x (as in 2-D), and the same closed-form `block_bits` rate model
    uses the 3-D coder constants (w = ceil(log2(64+1)) = 7)."""
    _, bm, bn = x_ref.shape
    Tt = [list(r) for r in zip(*T)]
    eb = eb_ref[0]
    planes = [x_ref[p] for p in range(4)]
    amax = functools.reduce(jnp.maximum, [jnp.abs(p) for p in planes])
    mx = jnp.maximum(_block_max(amax, (0, 1)), 1e-30)
    e = jnp.ceil(jnp.log2(mx))
    scale = jnp.exp2(-e)
    cz = _plane_transform([p * scale for p in planes], T)
    c = [_transform(_transform(p, T, 0), T, 1) for p in cz]
    trunc = [_truncate(p, e, eb, gain3) for p in c]
    nsb = [t[0] for t in trunc]
    # rate model (see module docstring): header + w*maxplane + sum nsb + 2*nsig
    w = math.ceil(math.log2(64 + 1))
    maxp = _block_max(functools.reduce(jnp.maximum, nsb), (0, 1))
    per = sum(_share(p, maxp, w, 64) for p in nsb)
    bits_ref[...] = (BLOCK_HEADER_BITS + _block_sums(per, bm, bn))[None]
    # inverse transform (z, y, x) + de-normalization
    for p, r in enumerate(_plane_transform([t[1] for t in trunc], Tt)):
        recon_ref[p] = _transform(_transform(r, Tt, 0), Tt, 1) / scale


@functools.partial(jax.jit, static_argnames=("transform", "block", "interpret"))
def bot3d_fused(
    x: jax.Array,
    eb: jax.Array | float,
    transform: str = "zfp",
    *,
    block: tuple[int, int, int],
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Fused ZFP-style transform+truncate for a 3-D f32 field.

    Returns (reconstruction (z, m, n) f32, bits (z/4, m/4, n/4) f32).
    Requires shape divisible by `block` (ops.py pads).
    """
    gain3 = float(bot_linf_gain(transform) ** 3)
    kernel = functools.partial(_bot3d_kernel, T=_matrix(transform), gain3=gain3)
    return _launch(kernel, x, eb, block, interpret)
