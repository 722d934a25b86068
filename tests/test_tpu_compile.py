"""The main path compiles for a TPU v5e at its real sizes.

Nothing runs: the TPU compiler, installed with jaxlib, compiles for a
described ``v5e:2x2`` that is not attached.  It refuses what the chip
would refuse (block shapes off the (8, 128) tiling, programs that do not
fit the device), which interpret mode and the CPU backend never do.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and test workers each import
every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks import vit
from repro.kernels import ops

BATCH, HW, OUT_HW = 128, (256, 256), (224, 224)
DEVICE_BUDGET = 15e9  # bytes of v5e's 16 GB that one program may take


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return ma.argument_size_in_bytes + ma.temp_size_in_bytes


def test_main_path_decode_compiles_for_v5e(one_chip):
    def decode(x, mean, std, flip, crop):
        return ops.dequant_normalize_augment(x, mean, std, flip, crop, out_hw=OUT_HW)

    compiled = jax.jit(decode).lower(
        _spec((BATCH, *HW, 3), jnp.uint8, one_chip),
        _spec((3,), jnp.float32, one_chip),
        _spec((3,), jnp.float32, one_chip),
        _spec((BATCH,), jnp.int32, one_chip),
        _spec((BATCH, 2), jnp.int32, one_chip),
    ).compile()
    assert compiled.out_info.shape == (BATCH, 3, *OUT_HW)
    assert compiled.out_info.dtype == jnp.bfloat16
    assert compiled.output_shardings.device_set == one_chip.device_set
    assert _bytes(compiled) < DEVICE_BUDGET


def test_vit_b16_step_compiles_for_v5e_at_batch_128(one_chip):
    cfg = vit.VIT_B16
    shapes = jax.eval_shape(lambda k: vit.init(cfg, k, OUT_HW), jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(lambda a: _spec(a.shape, a.dtype, one_chip), shapes)
    compiled = vit.make_step(cfg).lower(
        params,
        _spec((BATCH, 3, *OUT_HW), jnp.bfloat16, one_chip),
        _spec((BATCH,), jnp.int32, one_chip),
    ).compile()
    assert _bytes(compiled) < DEVICE_BUDGET
