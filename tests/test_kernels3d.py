"""3-D Pallas kernel tier vs jnp oracles (DESIGN.md §3.4–§3.5), plus the
shared dispatch predicate that keeps the Lorenzo and BOT wrappers routing
the same fields to the same tier."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import embedded
from repro.core.transforms import block_transform_nd, bot_linf_gain, bot_matrix
from repro.kernels import ops, ref


def _field(shape, seed, kind="walk"):
    rng = np.random.default_rng(seed)
    if kind == "walk":
        return jnp.asarray(
            np.cumsum(rng.standard_normal(shape), axis=-1).astype(np.float32)
        )
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


#: tile multiples, clamped-tile shapes, and ragged padded-edge shapes
SHAPES3 = [(16, 128, 256), (32, 96, 96), (13, 50, 67), (8, 130, 259)]
#: legal tiles span the whole last dim; rows are multiples of 8
BLOCKS3 = [(8, 128, 256), (8, 32, 256), (4, 16, 256)]


@pytest.mark.parametrize("shape", SHAPES3)
@pytest.mark.parametrize("kind", ["walk", "noise"])
def test_lorenzo3d_kernel_matches_ref(shape, kind):
    x = _field(shape, 0, kind)
    eb = 1e-3 * float(jnp.max(x) - jnp.min(x))
    got = ops.lorenzo_encode(x, eb)
    want = ref.lorenzo3d_encode_ref(x, eb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block", BLOCKS3)
def test_lorenzo3d_kernel_block_sweep(block):
    x = _field((16, 128, 256), 1)
    eb = 1e-3 * float(jnp.max(x) - jnp.min(x))
    got = ops.lorenzo_encode(x, eb, block=block)
    want = ref.lorenzo3d_encode_ref(x, eb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("eb_rel", [1e-2, 1e-3, 1e-5])
def test_lorenzo3d_roundtrip_bound(eb_rel):
    x = _field((12, 60, 77), 2)
    eb = eb_rel * float(jnp.max(x) - jnp.min(x))
    codes = ops.lorenzo_encode(x, eb)
    # decode-side parity: kernel dequantize == reference decode, bit-exact
    rec = ops.lorenzo_decode(codes, eb)
    want = ref.lorenzo3d_decode_ref(codes, eb)
    np.testing.assert_array_equal(np.asarray(rec), np.asarray(want))
    tol = eb + 4 * float(np.spacing(np.float32(float(jnp.max(jnp.abs(x))))))
    assert float(jnp.max(jnp.abs(rec - x))) <= tol


@pytest.mark.parametrize("shape", SHAPES3)
@pytest.mark.parametrize("transform", ["zfp", "hwt", "dct2"])
def test_bot3d_kernel_matches_ref(shape, transform):
    x = _field(shape, 3)
    eb = 1e-3 * float(jnp.max(x) - jnp.min(x))
    got_r, got_b = ops.bot_fused(x, eb, transform=transform)
    z, m, n = shape
    xp = jnp.pad(x, tuple((0, (-s) % 4) for s in shape))
    want_r, want_b = ref.bot3d_fused_ref(xp, eb, transform=transform)
    np.testing.assert_allclose(
        np.asarray(got_r),
        np.asarray(want_r)[:z, :m, :n],
        atol=1e-5 * float(jnp.max(jnp.abs(x))),
    )
    np.testing.assert_allclose(
        np.asarray(got_b),
        np.asarray(want_b)[: -(-z // 4), : -(-m // 4), : -(-n // 4)],
        rtol=1e-6,
    )


def test_bot3d_block_bits_agreement():
    """The kernel's in-tile closed-form rate model must equal
    `embedded.block_bits` evaluated on the same coefficients — the
    selector's §5 coder model and the kernel tier cannot drift apart.
    Coefficients are rebuilt with the kernel's own contraction (one
    einsum) so the comparison is exact: a different contraction order
    shifts knife-edge coefficients across a bit-plane boundary, which is
    contraction ulps, not a rate-model difference."""
    x = _field((16, 96, 128), 4)  # 4-multiples: blockize pads nothing
    eb = 1e-3 * float(jnp.max(x) - jnp.min(x))
    _, got_b = ops.bot_fused(x, eb)
    z, m, n = x.shape
    b = x.reshape(z // 4, 4, m // 4, 4, n // 4, 4).transpose(0, 2, 4, 1, 3, 5)
    blocks = b.reshape(-1, 4, 4, 4)
    norm, e = embedded.align_blocks(blocks)
    T = jnp.asarray(bot_matrix("zfp"), jnp.float32)
    coeffs = jnp.einsum("ai,bj,ck,xijk->xabc", T, T, T, norm)
    step = embedded.plane_step(jnp.float32(eb), e, bot_linf_gain("zfp") ** 3)
    want = np.asarray(embedded.block_bits(coeffs, step))
    got = np.asarray(got_b).reshape(-1)
    # a coefficient sitting exactly on a bit-plane boundary can gain/lose
    # one significant bit under a different einsum lowering; everything
    # else must match the closed-form model exactly
    diff = np.abs(got - want)
    assert np.mean(diff > 0) < 5e-3, f"{np.mean(diff > 0):.4f} blocks differ"
    assert diff.max() <= 8.0, "beyond a knife-edge plane flip: model drifted"
    assert abs(float(np.mean(got - want))) / 64.0 < 1e-4  # bits/value
    # and the selector's generic transform path agrees to the same ulps
    coeffs2 = block_transform_nd(norm, T, 3)
    want2 = np.asarray(embedded.block_bits(coeffs2, step))
    assert abs(float(np.mean(want2 - got))) / 64.0 < 1e-4  # bits/value


def test_bot3d_error_bound():
    x = _field((32, 64, 64), 5)
    eb = 1e-3 * float(jnp.max(x) - jnp.min(x))
    rec, _ = ops.bot_fused(x, eb)
    assert float(jnp.max(jnp.abs(rec - x))) <= eb


def test_dispatch_predicate_shared():
    """The ISSUE-4 bugfix: ONE predicate decides the kernel tier for both
    wrappers, so no field encodes on one path and prices on another.
    Short leading dims (a 4-token KV page, a 7-plane volume) stay on the
    kernel tier via sublane padding — `bot_compress_kv` relies on real
    per-block bits for every 2-D/3-D page."""
    assert ops.pallas_rank((256, 256)) == 2
    assert ops.pallas_rank((96, 256, 256)) == 3
    assert ops.pallas_rank((4, 40)) == 2  # short pages pad into the tier
    assert ops.pallas_rank((7, 64, 64)) == 3
    assert ops.pallas_rank((4096,)) is None
    assert ops.pallas_rank((0, 40)) is None  # empty: nothing to tile
    assert ops.pallas_rank((2, 3, 8, 32, 32)) is None  # >3-D: fold first
    for shape in [(4, 40), (8, 40), (7, 64, 64), (8, 64, 64), (4096,)]:
        x = _field(shape, 6)
        eb = 1e-3 * float(jnp.max(x) - jnp.min(x))
        # lorenzo agrees with the rank-generic reference on BOTH paths
        np.testing.assert_array_equal(
            np.asarray(ops.lorenzo_encode(x, eb)),
            np.asarray(ref.lorenzo_encode_ref(x, eb)),
        )
        # bot reports per-block bits exactly when the kernel tier serves
        # the shape — the same predicate, observable from outside
        _, bits = ops.bot_fused(x, eb)
        assert (bits is not None) == (ops.pallas_rank(shape) is not None), shape


def test_fold_plans_keep_3d_fields_3d():
    """Genuinely-3-D fields must reach the kernel tier as 3-D views; only
    rank > 3 folds (to 3-D, never to 2-D) and short leading dims merge."""
    from repro.launch.shapes import compression_view

    assert compression_view((96, 256, 256)) == (96, 256, 256)
    assert compression_view((8, 64, 64, 64)) == (512, 64, 64)
    assert compression_view((2, 3, 8, 32, 32)) == (48, 32, 32)
    assert compression_view((2, 96, 96)) == (192, 96)  # z < 4: no 4-block
    assert ops.pallas_rank(compression_view((8, 64, 64, 64))) == 3


def test_kernels3d_are_jittable_and_lowerable():
    """The 3-D kernels must lower+compile under jit (TPU-target health)."""
    x = jax.ShapeDtypeStruct((16, 128, 256), jnp.float32)
    c1 = jax.jit(lambda a: ops.lorenzo_encode(a, 1e-3)).lower(x).compile()
    assert c1.cost_analysis() is not None
    c2 = jax.jit(lambda a: ops.bot_fused(a, 1e-3)).lower(x).compile()
    assert c2 is not None


def test_select_3d_batched_matches_per_field():
    """Batched 3-D decisions == per-field reference decisions (Stage I/II
    over 4x4x4 blocks; acceptance criterion of ISSUE 4)."""
    from benchmarks.common import hurricane_suite, nyx_suite
    from repro.core import select, select_many

    fields = list(hurricane_suite(4, size=(16, 48, 48)).values())
    fields += list(nyx_suite(3, size=(32, 32, 32)).values())
    many = select_many(fields, eb_rel=1e-3)
    for f, m in zip(fields, many):
        s = select(f, eb_abs=float(m.eb_abs))
        assert m.codec == s.codec
        assert m.eb_sz == pytest.approx(s.eb_sz, rel=1e-6)
