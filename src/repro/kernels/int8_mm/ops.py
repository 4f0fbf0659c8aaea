"""jit'd wrapper: quantize → int8 MXU GEMM → dequant.

``int8_matmul(x, w)`` is the end-to-end op: symmetric per-row quantization of
``x``, per-column of ``w`` (the paper's fixed-8-bit operand adjustment with
the finer granularity TPU int8 kernels conventionally use), then the fused
Pallas GEMM.  ``int8_mm_pallas`` is the raw quantized-operand entry point.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.int8_mm.int8_mm import int8_mm_pallas_call

__all__ = ["int8_mm_pallas", "int8_matmul"]


def _pad(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret"))
def int8_mm_pallas(a, w, scale_a, scale_w, *, block_m=128, block_n=128,
                   block_k=128, interpret=None):
    """a int8 [M,K], w int8 [K,N], scales f32 [M]/[N] → f32 [M,N]."""
    M, K = a.shape
    _, N = w.shape
    bm, bn, bk = (min(block_m, M), min(block_n, N), min(block_k, K))
    a2 = _pad(_pad(a, 0, bm), 1, bk)
    w2 = _pad(_pad(w, 0, bk), 1, bn)
    sa = _pad(scale_a.reshape(-1, 1).astype(jnp.float32), 0, bm)
    sw = _pad(scale_w.reshape(1, -1).astype(jnp.float32), 1, bn)
    y = int8_mm_pallas_call(a2, w2, sa, sw, block_m=bm, block_n=bn, block_k=bk,
                            interpret=interpret)
    return y[:M, :N]


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_matmul(x: jax.Array, w: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """fp [M,K] @ fp [K,N] through symmetric int8 quantization (per-row/col)."""
    amax_x = jnp.maximum(jnp.abs(x).max(axis=1, keepdims=True), 1e-12)
    amax_w = jnp.maximum(jnp.abs(w).max(axis=0, keepdims=True), 1e-12)
    sx = (amax_x / 127.0).astype(jnp.float32)
    sw = (amax_w / 127.0).astype(jnp.float32)
    xq = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int8)
    wq = jnp.clip(jnp.round(w / sw), -127, 127).astype(jnp.int8)
    return int8_mm_pallas(xq, wq, sx[:, 0], sw[0, :], interpret=interpret)
