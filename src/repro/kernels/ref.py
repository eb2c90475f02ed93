"""Pure-jnp oracles for the Pallas kernels (allclose-validated in tests)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.transforms import bot_linf_gain, bot_matrix, lorenzo_forward, lorenzo_inverse


def lorenzo_encode_ref(x: jax.Array, eb: jax.Array | float) -> jax.Array:
    """round(x/2eb) then n-D integer Lorenzo difference."""
    delta = 2.0 * jnp.asarray(eb, jnp.float32)
    k = jnp.round(x.astype(jnp.float32) / delta)
    return lorenzo_forward(k).astype(jnp.int32)


def lorenzo_decode_ref(d: jax.Array, eb: jax.Array | float) -> jax.Array:
    """Inverse: n-D cumsum of codes, then dequantize."""
    delta = 2.0 * jnp.asarray(eb, jnp.float32)
    k = lorenzo_inverse(d.astype(jnp.float32))
    return k * delta


#: rank-specific aliases kept for the existing kernel parity tests — the
#: reference is rank-generic (`lorenzo_forward` folds per axis)
lorenzo2d_encode_ref = lorenzo_encode_ref
lorenzo2d_decode_ref = lorenzo_decode_ref
lorenzo3d_encode_ref = lorenzo_encode_ref
lorenzo3d_decode_ref = lorenzo_decode_ref


def _transform_axis(v: jax.Array, T, axis: int) -> jax.Array:
    """The 4-point transform along one block axis: out[a] = sum_b T[a][b]
    v[b], summed in b order — the kernels apply T in the same operation
    order, so their coefficients match these bit for bit."""
    parts = [jnp.take(v, b, axis=axis) for b in range(4)]
    out = []
    for a in range(4):
        acc = T[a][0] * parts[0]
        for b in range(1, 4):
            acc = acc + T[a][b] * parts[b]
        out.append(acc)
    return jnp.stack(out, axis=axis)


def _matrices(transform: str):
    T = np.asarray(bot_matrix(transform), np.float32)
    return T.tolist(), T.T.tolist()


@functools.partial(jax.jit, static_argnames=("transform",))
def bot2d_fused_ref(
    x: jax.Array, eb: jax.Array | float, transform: str = "zfp"
) -> tuple[jax.Array, jax.Array]:
    """Blockize -> align -> BOT -> truncate -> (recon, bits/block)."""
    m, n = x.shape
    assert m % 4 == 0 and n % 4 == 0
    T, Tt = _matrices(transform)
    gain2 = float(bot_linf_gain(transform) ** 2)
    b = x.astype(jnp.float32).reshape(m // 4, 4, n // 4, 4).transpose(0, 2, 1, 3)
    mx = jnp.maximum(jnp.max(jnp.abs(b), axis=(2, 3)), 1e-30)
    e = jnp.ceil(jnp.log2(mx))
    scale = jnp.exp2(-e)[..., None, None]
    norm = b * scale
    c = _transform_axis(_transform_axis(norm, T, 2), T, 3)
    raw = jnp.asarray(eb, jnp.float32) / (jnp.exp2(e) * gain2)
    step = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(raw, 2.0**-60))))[..., None, None]
    q = jnp.abs(c) / step
    mm = jnp.trunc(q)
    nsb = jnp.where(mm >= 1.0, jnp.floor(jnp.log2(jnp.maximum(mm, 1.0))) + 1.0, 0.0)
    w = math.ceil(math.log2(17))
    sig = jnp.sum(nsb, axis=(2, 3))
    nsig = jnp.sum((nsb > 0.0).astype(jnp.float32), axis=(2, 3))
    maxp = jnp.max(nsb, axis=(2, 3))
    bits = 24.0 + w * maxp + sig + 2.0 * nsig
    rc = jnp.sign(c) * jnp.where(mm > 0, (mm + 0.5) * step, 0.0)
    rb = _transform_axis(_transform_axis(rc, Tt, 2), Tt, 3)
    rb = rb / scale
    recon = rb.transpose(0, 2, 1, 3).reshape(m, n)
    return recon, bits


@functools.partial(jax.jit, static_argnames=("transform",))
def bot3d_fused_ref(
    x: jax.Array, eb: jax.Array | float, transform: str = "zfp"
) -> tuple[jax.Array, jax.Array]:
    """4x4x4 blockize -> align -> BOT -> truncate -> (recon, bits/block)."""
    z, m, n = x.shape
    assert z % 4 == 0 and m % 4 == 0 and n % 4 == 0
    T, Tt = _matrices(transform)
    gain3 = float(bot_linf_gain(transform) ** 3)
    b = (
        x.astype(jnp.float32)
        .reshape(z // 4, 4, m // 4, 4, n // 4, 4)
        .transpose(0, 2, 4, 1, 3, 5)
    )
    mx = jnp.maximum(jnp.max(jnp.abs(b), axis=(3, 4, 5)), 1e-30)
    e = jnp.ceil(jnp.log2(mx))
    scale = jnp.exp2(-e)[..., None, None, None]
    norm = b * scale
    c = norm
    for ax in (3, 4, 5):
        c = _transform_axis(c, T, ax)
    raw = jnp.asarray(eb, jnp.float32) / (jnp.exp2(e) * gain3)
    step = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(raw, 2.0**-60))))[
        ..., None, None, None
    ]
    q = jnp.abs(c) / step
    mm = jnp.trunc(q)
    nsb = jnp.where(mm >= 1.0, jnp.floor(jnp.log2(jnp.maximum(mm, 1.0))) + 1.0, 0.0)
    w = math.ceil(math.log2(65))
    sig = jnp.sum(nsb, axis=(3, 4, 5))
    nsig = jnp.sum((nsb > 0.0).astype(jnp.float32), axis=(3, 4, 5))
    maxp = jnp.max(nsb, axis=(3, 4, 5))
    bits = 24.0 + w * maxp + sig + 2.0 * nsig
    rc = jnp.sign(c) * jnp.where(mm > 0, (mm + 0.5) * step, 0.0)
    rb = rc
    for ax in (3, 4, 5):
        rb = _transform_axis(rb, Tt, ax)
    rb = rb / scale
    recon = rb.transpose(0, 3, 1, 4, 2, 5).reshape(z, m, n)
    return recon, bits
