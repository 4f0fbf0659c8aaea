"""jit'd entry point for paged decode attention.

``paged_attention(q, k_pool, v_pool, tables, lengths)`` is the op the serving
decode path calls per layer: GQA head grouping, kernel dispatch (interpreted
off the TPU, so tier-1 tests run on CPU).  ``use_kernel=False``
routes to the pure-jnp oracle (ref.py) for debugging.

``q`` may carry a small leading query axis (``[B, Q, H, D]``, the speculative
verify tile): the Q tokens are packed into the kernel's query tile and
causally masked per row — one dispatch scores a whole draft.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attn.paged_attn import paged_attn_pallas_call
from repro.kernels.paged_attn.ref import paged_attn_ref

__all__ = ["paged_attention"]


@functools.partial(jax.jit, static_argnames=("window", "kv_scale",
                                             "use_kernel", "interpret"))
def paged_attention(q, k_pool, v_pool, tables, lengths, *, window: int = 0,
                    kv_scale=None, use_kernel: bool = True,
                    interpret=None) -> jax.Array:
    """q [B, H, D] (decode) or [B, Q, H, D] (Q-token verify) against pools
    [N, bs, H_kv, D] via tables [B, P] → output of q's shape.

    ``lengths [B]`` counts visible tokens per sequence *including every query
    token* (each query's K/V must already be written; query ``j`` of Q sits
    at absolute position ``lengths - Q + j`` and attends causally).
    ``kv_scale`` set ⇒ pools hold fixed-point int8 (values/kv_scale).
    ``interpret=None`` picks compiled on TPU, interpreter everywhere else
    (``repro.kernels.backend.resolve_interpret``).
    """
    if q.ndim == 3:
        B, H, D = q.shape
        Q = 1
    else:
        B, Q, H, D = q.shape
    Hkv = k_pool.shape[2]
    if H % Hkv:
        raise ValueError(f"n_heads {H} not a multiple of n_kv_heads {Hkv}")
    G = H // Hkv
    if q.ndim == 3:
        qt = q.reshape(B, Hkv, G, D)
    else:
        # pack the Q tokens into the query tile: row q·G + g
        # [B, Q, Hkv, G, D] → [B, Hkv, Q, G, D] → [B, Hkv, Q·G, D]
        qt = q.reshape(B, Q, Hkv, G, D).transpose(0, 2, 1, 3, 4)
        qt = qt.reshape(B, Hkv, Q * G, D)
    tables = tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    if use_kernel:
        o = paged_attn_pallas_call(qt, k_pool, v_pool, tables, lengths,
                                   window=window, kv_scale=kv_scale,
                                   q_len=Q, interpret=interpret)
    else:
        o = paged_attn_ref(qt, k_pool, v_pool, tables, lengths,
                           window=window, kv_scale=kv_scale, q_len=Q)
    if q.ndim == 3:
        return o.reshape(B, H, D)
    return o.reshape(B, Hkv, Q, G, D).transpose(0, 2, 1, 3, 4).reshape(B, Q, H, D)
