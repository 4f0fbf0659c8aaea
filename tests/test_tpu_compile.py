"""The serving path's paged-attention kernel compiles for a TPU v5e.

Compiles (nothing runs) against a described v5e chip at phi4-mini-3.8b
decode shapes, so a kernel Mosaic would refuse fails here instead of on the
chip.  The topology is described inside a fixture, never at import time:
only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attn import paged_attention
from repro.nn.attention import KV_SCALE

# phi4-mini-3.8b: 24 query heads over 8 kv heads of 128; serving slots 8,
# max_len 2048 in 16-row blocks
B, H, HKV, D, BS, PAGES = 8, 24, 8, 128, 16, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the cache but cannot be
    read back without one; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("q_len,pool_dtype", [
    (1, jnp.bfloat16),      # decode
    (5, jnp.bfloat16),      # speculative verify, K=4 drafts
    (1, jnp.int8),          # ODIN fixed-8-bit KV pool
])
def test_paged_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          q_len, pool_dtype):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    n_blocks = B * PAGES + 1
    q_shape = (B, H, D) if q_len == 1 else (B, q_len, H, D)
    kv_scale = KV_SCALE if pool_dtype == jnp.int8 else None
    fn = jax.jit(lambda q, k, v, t, n: paged_attention(
        q, k, v, t, n, kv_scale=kv_scale, interpret=False))
    compiled = fn.lower(
        sds(q_shape, jnp.bfloat16),
        sds((n_blocks, BS, HKV, D), pool_dtype),
        sds((n_blocks, BS, HKV, D), pool_dtype),
        sds((B, PAGES), jnp.int32),
        sds((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
