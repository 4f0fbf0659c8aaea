"""jit'd public wrapper for the fused stochastic-MAC kernel.

``sc_matmul_pallas`` keeps the same operand signature as the jnp reference
(`core.stochastic.sc_matmul`): packed LUTs in, popcounts out.  It recovers
the comparator-SNG rank vectors from the LUTs (bit-exact round trip) and
dispatches:

* ``K̂ ≤ max_tree_k``   — single K tile, full MUX tree: output int32, equal
  bit-for-bit to ``sc_matmul``.
* ``K̂ > max_tree_k``   — tiled hybrid (per-tile tree + binary accumulate),
  rescaled to full-tree popcount units (× K̂_t/K̂) so callers see one scale;
  output float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stochastic as sc
from repro.kernels.sc_mac.ref import ranks_from_lut
from repro.kernels.sc_mac.sc_mac import sc_mac_pallas_call

__all__ = ["sc_matmul_pallas"]


def _pad_axis(x, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "interpret", "block_m", "block_n", "max_tree_k"),
)
def sc_matmul_pallas(
    a_q: jax.Array,          # uint8/int32 [M, K]
    w_q: jax.Array,          # uint8/int32 [K, N]
    lut_a: jax.Array,
    lut_w: jax.Array,
    selects: jax.Array,
    spec: sc.StreamSpec = sc.StreamSpec(),
    *,
    interpret: bool | None = None,
    block_m: int = 8,
    block_n: int = 8,
    max_tree_k: int = 2048,
) -> jax.Array:
    """Fused ODIN MAC array.  See module docstring for the two regimes."""
    M, K = a_q.shape
    _, N = w_q.shape
    khat = 1 << sc.tree_depth(K)

    ra = ranks_from_lut(lut_a, spec.n_levels)
    rw = ranks_from_lut(lut_w, spec.n_levels)

    a = _pad_axis(a_q.astype(jnp.int32), 0, block_m)
    w = _pad_axis(w_q.astype(jnp.int32), 1, block_n)

    if khat <= max_tree_k:
        block_k = khat
        a = _pad_axis(a, 1, block_k)
        w = _pad_axis(w, 0, block_k)
        out = sc_mac_pallas_call(
            a, w, ra, rw, selects,
            block_m=block_m, block_n=block_n, block_k=block_k, interpret=interpret,
        )
        return out[:M, :N]

    block_k = max_tree_k
    a = _pad_axis(a, 1, block_k)
    w = _pad_axis(w, 0, block_k)
    out = sc_mac_pallas_call(
        a, w, ra, rw, selects,
        block_m=block_m, block_n=block_n, block_k=block_k, interpret=interpret,
    )
    # rescale per-tile popcount units (K̂_t) to full-tree units (K̂)
    return out[:M, :N].astype(jnp.float32) * (block_k / khat)
