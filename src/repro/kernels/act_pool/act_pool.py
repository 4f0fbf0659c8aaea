"""Fused 8-bit ReLU + p×p max-pool — ODIN's binary-domain add-on blocks.

The paper implements activation and pooling as CMOS logic *after* the
popcount (§IV-B.2): an 8-bit ReLU block and a 4:1 max-pool block, operating
in the binary domain (the hybrid boundary).  On TPU both are elementwise /
small-window VPU ops, so the natural mapping is one fused epilogue kernel
applied to the popcount (S_TO_B) output tile:

    y[b, i, j, c] = max_{2×2 window} clip(x, 0, 255)

Input is the int32 popcount-domain feature map NHWC; output is the pooled
uint8-range int32 map (values 0..255, the paper's 8-bit activations).  The
kernel blocks over (batch, channel) and keeps whole H×W planes in VMEM —
paper-scale planes (≤224×224) are ≤1.6 MB/block at bc=8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

__all__ = ["act_pool_kernel", "act_pool_pallas_call"]


def _activate(x: jax.Array, act: str) -> jax.Array:
    """The paper's §IV-B.2 extensibility point: relu (clip) or 8-bit tanh —
    the 256-entry LUT a CMOS tanh block stores, in closed VPU form."""
    if act == "tanh":
        y = jnp.round(255.0 * jnp.tanh(x.astype(jnp.float32) / 64.0))
        return jnp.clip(y, 0, 255).astype(jnp.int32)
    return jnp.clip(x, 0, 255)                    # saturating 8-bit ReLU


def act_pool_kernel(x_ref, out_ref, *, pool: int, act: str = "relu",
                    pool_kind: str = "max"):
    """x int32 [1, H, W, bc] → out int32 [1, H/p, W/p, bc]."""
    x = x_ref[...]
    r = _activate(x, act)
    _, H, W, C = x.shape
    p = pool
    r = r.reshape(1, H // p, p, W // p, p, C)
    if pool_kind == "avg":                        # §IV-B.2 average pooling
        out_ref[...] = jnp.round(
            r.sum(axis=(2, 4)).astype(jnp.float32) / (p * p)
        ).astype(jnp.int32)
    else:
        out_ref[...] = r.max(axis=(2, 4))


def act_pool_pallas_call(
    x: jax.Array,            # int32 [B, H, W, C], H % pool == W % pool == 0
    *,
    pool: int = 2,
    block_c: int = 8,
    act: str = "relu",
    pool_kind: str = "max",
    interpret: bool | None = None,
) -> jax.Array:
    B, H, W, C = x.shape
    assert H % pool == 0 and W % pool == 0, (H, W, pool)
    assert C % block_c == 0, (C, block_c)
    kernel = functools.partial(act_pool_kernel, pool=pool, act=act,
                               pool_kind=pool_kind)
    return pl.pallas_call(
        kernel,
        grid=(B, C // block_c),
        in_specs=[pl.BlockSpec((1, H, W, block_c), lambda b, c: (b, 0, 0, c))],
        out_specs=pl.BlockSpec((1, H // pool, W // pool, block_c), lambda b, c: (b, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, H // pool, W // pool, C), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x)
