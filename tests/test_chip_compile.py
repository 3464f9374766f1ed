"""The snapshot kernels compile for a TPU v5e that is described, not
attached: the TPU compiler installed with JAX refuses here what the chip
would refuse (block shapes, scalar stores, memory), at no chip time.

The topology is described only inside a fixture, so importing this file
loads no TPU library; every compile runs in the test's own process.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.delta_encode.kernel import (LANE, SUB, changed_bitmap,
                                               delta_apply, delta_encode,
                                               fused_delta_tiles)

# 12,289 tiles of 8 x 1024: granite-3-2b's 49155 x 2048 float32 embedding
FUSED_TILE_COUNTS = [1, 256, 12289]
LEAF = (1000, 517)    # a float32 leaf whose last tile is partial


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("nblk", FUSED_TILE_COUNTS)
def test_fused_delta_tiles_compiles(one_chip, nblk):
    tiles = _spec((nblk, SUB, LANE), jnp.int32, one_chip)
    _assert_kernel(fused_delta_tiles.lower(tiles, tiles).compile())


@pytest.mark.parametrize("kernel", [changed_bitmap, delta_encode],
                         ids=["changed_bitmap", "delta_encode"])
def test_probe_kernels_compile(one_chip, kernel):
    leaf = _spec(LEAF, jnp.float32, one_chip)
    _assert_kernel(kernel.lower(leaf, leaf).compile())


def test_delta_apply_compiles(one_chip):
    leaf = _spec(LEAF, jnp.float32, one_chip)
    nblk = -(-LEAF[0] * LEAF[1] // (SUB * LANE))
    delta = _spec((nblk, SUB, LANE), jnp.int32, one_chip)
    _assert_kernel(delta_apply.lower(leaf, delta).compile())
