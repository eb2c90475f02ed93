"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _field(shape, seed, kind="walk"):
    rng = np.random.default_rng(seed)
    if kind == "walk":
        return jnp.asarray(np.cumsum(rng.standard_normal(shape), axis=-1).astype(np.float32))
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


SHAPES = [(256, 256), (512, 384), (300, 517), (64, 1024), (8, 128)]
#: legal tiles span the whole last dim; rows are multiples of 8
BLOCKS = [(256, 512), (128, 512), (8, 512)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("kind", ["walk", "noise"])
def test_lorenzo_kernel_matches_ref(shape, kind):
    x = _field(shape, 0, kind)
    eb = 1e-3 * float(jnp.max(x) - jnp.min(x))
    got = ops.lorenzo_encode(x, eb)
    want = ref.lorenzo2d_encode_ref(x, eb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block", BLOCKS)
def test_lorenzo_kernel_block_sweep(block):
    x = _field((512, 512), 1)
    eb = 1e-3 * float(jnp.max(x) - jnp.min(x))
    got = ops.lorenzo_encode(x, eb, block=block)
    want = ref.lorenzo2d_encode_ref(x, eb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("eb_rel", [1e-2, 1e-3, 1e-5])
def test_lorenzo_roundtrip_bound(eb_rel):
    x = _field((300, 200), 2)
    eb = eb_rel * float(jnp.max(x) - jnp.min(x))
    rec = ops.lorenzo_decode(ops.lorenzo_encode(x, eb), eb)
    tol = eb + 4 * float(np.spacing(np.float32(float(jnp.max(jnp.abs(x))))))
    assert float(jnp.max(jnp.abs(rec - x))) <= tol


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("transform", ["zfp", "hwt", "dct2"])
def test_bot_kernel_matches_ref(shape, transform):
    x = _field(shape, 3)
    eb = 1e-3 * float(jnp.max(x) - jnp.min(x))
    got_r, got_b = ops.bot_fused(x, eb, transform=transform)
    m, n = shape
    xp = jnp.pad(x, ((0, (-m) % 4), (0, (-n) % 4)))
    want_r, want_b = ref.bot2d_fused_ref(xp, eb, transform=transform)
    np.testing.assert_allclose(
        np.asarray(got_r), np.asarray(want_r)[:m, :n], atol=1e-5 * float(jnp.max(jnp.abs(x)))
    )
    np.testing.assert_allclose(
        np.asarray(got_b), np.asarray(want_b)[: -(-m // 4), : -(-n // 4)], rtol=1e-6
    )


def test_bot_kernel_error_bound():
    x = _field((256, 256), 4)
    eb = 1e-3 * float(jnp.max(x) - jnp.min(x))
    rec, _ = ops.bot_fused(x, eb)
    assert float(jnp.max(jnp.abs(rec - x))) <= eb


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lorenzo_dtype_sweep(dtype):
    x = _field((128, 128), 5).astype(dtype)
    eb = 1e-2 * float(jnp.max(x.astype(jnp.float32)) - jnp.min(x.astype(jnp.float32)))
    got = ops.lorenzo_encode(x.astype(jnp.float32), eb)
    want = ref.lorenzo2d_encode_ref(x.astype(jnp.float32), eb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernels_are_jittable_and_lowerable():
    """The kernels must lower+compile under jit (TPU-target path health)."""
    x = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    c1 = jax.jit(lambda a: ops.lorenzo_encode(a, 1e-3)).lower(x).compile()
    assert c1.cost_analysis() is not None
    c2 = jax.jit(lambda a: ops.bot_fused(a, 1e-3)).lower(x).compile()
    assert c2 is not None
