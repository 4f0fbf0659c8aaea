"""jit'd wrapper for the fused activation+pool kernel (channel padding)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.act_pool.act_pool import act_pool_pallas_call

__all__ = ["act_pool"]


@functools.partial(jax.jit, static_argnames=("pool", "act", "pool_kind", "interpret"))
def act_pool(x: jax.Array, *, pool: int = 2, act: str = "relu",
             pool_kind: str = "max", interpret: bool | None = None) -> jax.Array:
    """int32 [B,H,W,C] → int32 [B,H/p,W/p,C]: 8-bit act then p×p pooling.

    ``act``: relu | tanh (8-bit LUT form); ``pool_kind``: max | avg — the
    paper's §IV-B.2 extensibility variants, same fused add-on block."""
    B, H, W, C = x.shape
    bc = 8 if C % 8 == 0 else 1
    return act_pool_pallas_call(x, pool=pool, block_c=bc, act=act,
                                pool_kind=pool_kind, interpret=interpret)
