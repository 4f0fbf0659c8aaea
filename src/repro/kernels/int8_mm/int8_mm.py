"""int8×int8→int32 MXU GEMM with fused dequant epilogue.

This is the *deployment* path for ODIN's expected-value surrogate
(DESIGN.md §2): the stochastic pipeline's expectation is an integer dot with
fixed scaling, and on TPU the right execution unit for an integer dot is the
MXU, not bit-ops.  The kernel:

* accumulates ``int8×int8`` partial products in an int32 VMEM scratch tile
  across the K grid axis (exact — no fp accumulation error),
* on the last K step applies the dequant epilogue
  ``y = acc · scale_a[m] · scale_w[n]`` and writes fp32.

Block sizes default to MXU-native 128×128×128 (multiples of the 128-lane /
128×128 systolic geometry); the interpret-mode tests sweep smaller blocks.

VMEM at defaults: a 16 KB + w 16 KB + acc 64 KB + out 64 KB ≪ budget; the
grid is (M/bm, N/bn, K/bk) with K innermost (sequential revisiting of the
same output tile — the standard Pallas accumulation pattern).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

__all__ = ["int8_mm_kernel", "int8_mm_pallas_call"]


def int8_mm_kernel(a_ref, w_ref, sa_ref, sw_ref, out_ref, acc_ref, *, n_k: int):
    """a int8 [bm,bk] · w int8 [bk,bn] → out f32 [bm,bn] (dequantized)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.int32),
        w_ref[...].astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        scale = sa_ref[...] * sw_ref[...]                 # [bm,1]·[1,bn] → [bm,bn]
        out_ref[...] = acc_ref[...].astype(jnp.float32) * scale


def int8_mm_pallas_call(
    a: jax.Array,            # int8 [M, K]
    w: jax.Array,            # int8 [K, N]
    scale_a: jax.Array,      # f32 [M, 1] per-row activation scales
    scale_w: jax.Array,      # f32 [1, N] per-column weight scales
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    M, K = a.shape
    _, N = w.shape
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (M, N, K)
    n_k = K // block_k
    kernel = functools.partial(int8_mm_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(a, w, scale_a, scale_w)
