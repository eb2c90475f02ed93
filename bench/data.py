"""A configuration's snapshot, generated on the device from the seed.

`spectral_field` follows the generator of `chip_smoke.py` (a random field
with a k^slope power spectrum, complex64 FFTs on the device), with a fixed
amplitude per mode and none at k = 0; the per-field slopes,
nonlinearities and noise levels are those of `benchmarks/common.py`'s
suites, written into each configuration file.

Every seed draws work of one difficulty. At the steep slopes of these
suites a few hundred of the lowest modes carry most of a field's
variance, so their phases would set its range, and with it the
range-relative bound and the ratio, differently for every seed. The
modes below the configuration's `fixed_below` (cycles per grid cell)
take their phases from a key of the configuration's own; the seed draws
all the others, which are what the predictors and transforms see.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.cells import Field

#: the key of the large scales that every seed shares
LARGE_SCALE_KEY = 0


def _phase(key, shape) -> jax.Array:
    """Unit phases of a white field's FFT: Hermitian, so the field is real."""
    white = jnp.fft.fftn(jax.random.normal(key, shape, jnp.float32))
    return white / jnp.maximum(jnp.abs(white), 1e-30)


def spectral_field(shape, slope: float, key, nonlin=None, *, fixed_key=None,
                   fixed_below: float = 0.0) -> jax.Array:
    """Standardized random field with power spectrum k^slope, optionally
    log-normal ('exp') or rectified ('relu').

    Every mode has the amplitude k^(slope/2) and a phase from `key`, or
    from `fixed_key` where k < `fixed_below`, so all seeds share one
    spectrum and one large-scale structure, and differ in the features
    below it. The mean (k = 0) carries no amplitude: a large constant
    there would leave float32 too few bits for the field around it."""
    k2 = sum(
        jnp.fft.fftfreq(s, dtype=jnp.float32).reshape(
            [-1 if d == i else 1 for d in range(len(shape))]
        ) ** 2
        for i, s in enumerate(shape)
    )
    amp = jnp.where(k2 > 0, jnp.where(k2 > 0, k2, 1.0) ** (slope / 4.0), 0.0)
    phase = _phase(key, shape)
    if fixed_below > 0:
        # the mask is symmetric in k, so the mixed phases stay Hermitian
        phase = jnp.where(k2 < fixed_below**2, _phase(fixed_key, shape), phase)
    x = jnp.real(jnp.fft.ifftn(amp * phase))
    x = (x - x.mean()) / (x.std() + 1e-12)
    if nonlin == "exp":
        x = jnp.exp(x)
    elif nonlin == "relu":
        x = jnp.maximum(x, 0.0)
    return x.astype(jnp.float32)


def seed_key(seed: int) -> jax.Array:
    """A key from all 64 bits of the seed (`jax.random.key` keeps 32)."""
    s = int(seed) % 2**64
    return jax.random.fold_in(jax.random.key(s % 2**32), s >> 32)


def snapshot(shape: tuple[int, ...], fields: tuple[Field, ...], seed: int,
             fixed_below: float = 0.0) -> dict:
    """All fields of one snapshot in one jitted call; blocks until made."""

    @jax.jit
    def make(key):
        out = {}
        for i, f in enumerate(fields):
            k_field, k_noise = jax.random.split(jax.random.fold_in(key, i))
            k_fixed = jax.random.fold_in(jax.random.key(LARGE_SCALE_KEY), i)
            x = spectral_field(shape, f.slope, k_field, f.nonlin, fixed_key=k_fixed,
                               fixed_below=fixed_below)
            if f.noise:
                x = x + f.noise * jax.random.normal(k_noise, shape, jnp.float32)
            out[f.name] = x
        return out

    return jax.block_until_ready(make(seed_key(seed)))
