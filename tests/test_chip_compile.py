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


def test_expert_layer_compiles_at_mellum_widths(one_chip):
    """Mellum2's EP-8 share, forward and backward: 2 x 4,096 tokens routed
    over 64 experts, the grouped products of the 8 held."""
    from repro.configs.base import get_arch
    from repro.moe.moe import moe_apply, moe_specs
    cfg = get_arch("mellum2-12b-a2.5b-ep8")
    p = {k: _spec(s.shape, jnp.float32, one_chip)
         for k, s in moe_specs(cfg).items()}
    x = _spec((2, 4096, cfg.d_model), jnp.bfloat16, one_chip)

    def loss(p, x):
        return jnp.sum(moe_apply(p, x, cfg)[0].astype(jnp.float32))
    jax.jit(jax.grad(loss, (0, 1))).lower(p, x).compile()


@pytest.mark.parametrize("window", [1024, 0], ids=["sliding", "full"])
def test_blocked_attention_backward_keeps_no_scores(one_chip, window):
    """Mellum2's attention at 4,096 positions, forward and backward: the
    backward pass recomputes scores, so its temporaries stay far below
    the 8.6 GB that one layer's saved scores would take."""
    from repro.models.layers import blocked_attention
    q = _spec((2, 4096, 32, 128), jnp.bfloat16, one_chip)
    kv = _spec((2, 4096, 4, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(blocked_attention(
            q, k, v, causal=True, window=window, block_q=512,
            block_kv=512).astype(jnp.float32))
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
