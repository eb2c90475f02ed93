"""Compile guards: every Pallas kernel compiles for a TPU v5e at the widths
users run — the paper's three data sets at their SDRBench shapes
(CESM-ATM 1800x3600, Hurricane-ISABEL 100x500x500, NYX 512^3) and one
smollm-360m KV page stack (32 layers x 16 tokens x 5 heads*64) — and the
compiled program holds the Mosaic kernel (`tpu_custom_call`).

The chip is described, not attached: the TPU compiler runs here and
refuses what the chip would refuse (unaligned blocks, unsupported vector
shapes, VMEM overruns). The topology is described inside a fixture,
never at import time: only one process may load the TPU library, and
every test worker imports this module.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bot4, lorenzo

ATM = (1800, 3600)
HURRICANE = (100, 500, 500)
NYX = (512, 512, 512)
KV_PAGES = (32, 16, 320)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but can never be read back here: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler to describe it with
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, shape, block, sharding, dtype=jnp.float32):
    padded = tuple(-(-s // b) * b for s, b in zip(shape, block))
    x = jax.ShapeDtypeStruct(padded, dtype, sharding=sharding)
    compiled = jax.jit(
        lambda a: fn(a, 1e-3, block=block, interpret=False)
    ).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("shape", [ATM, HURRICANE, NYX, KV_PAGES])
def test_lorenzo_encode_compiles(one_chip, shape):
    fn = lorenzo.lorenzo2d_encode if len(shape) == 2 else lorenzo.lorenzo3d_encode
    _compile(fn, shape, lorenzo.tile(shape), one_chip)


@pytest.mark.parametrize("shape", [ATM, HURRICANE, NYX])
def test_dequantize_compiles(one_chip, shape):
    _compile(lorenzo.dequantize, shape, lorenzo.tile(shape), one_chip, jnp.int32)


@pytest.mark.parametrize("shape", [ATM, HURRICANE, NYX, KV_PAGES])
def test_bot_fused_compiles(one_chip, shape):
    fn = bot4.bot2d_fused if len(shape) == 2 else bot4.bot3d_fused
    _compile(fn, shape, bot4.tile(shape), one_chip)


def test_device_sz_pack_fits_one_chip(one_chip):
    """The device SZ encoder's packing pass (`device_encode=True`) at NYX
    512^3 fits the chip's 16 GiB (a (words, window) temporary once padded
    to 32 GiB). Its Lorenzo pass is the kernel compiled above."""
    from repro.core import device_encode as de

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lut = (de.N_SYMBOLS,)
    syms = (NYX[0] * NYX[1] * NYX[2],)  # pass 1 hands pass 2 the flat stream
    p2 = de._sz_pass2.lower(
        sds(syms, jnp.int32), sds(NYX, jnp.int32), sds(lut, jnp.uint32), sds(lut, jnp.int32),
        n_words=1 << 26, esc_cap=1 << 10, window=6,
    ).compile()
    mem = p2.memory_analysis()
    total = mem.temp_size_in_bytes + mem.argument_size_in_bytes + mem.output_size_in_bytes
    assert total < 12 * 2**30, total
