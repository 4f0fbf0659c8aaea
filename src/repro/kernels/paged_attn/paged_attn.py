"""Paged decode-attention Pallas kernel: attend over a device block pool.

The physical KV store is a single pool ``[n_blocks, block_size, H_kv, d_head]``
shared by every serving slot; each slot owns a *block table* mapping its
logical pages to pool blocks.  The kernel indexes the table **inside** the
compiled step, so decode reads K/V blocks in place — no dense
``[slots, max_len]`` live cache, no gather materialization; device KV memory
scales with ``n_blocks·block_size`` (≈ active tokens) instead of
``slots × max_len``.

Layout:

* grid ``(B, n_pages)`` with the page axis innermost — the online softmax
  state (m, l, acc) for every kv head lives in VMEM scratch carried across
  pages;
* ``lengths [B]`` and ``tables [B, n_pages]`` are **scalar-prefetched**: the
  K/V BlockSpec index maps read ``tables[b, i]`` to pull page ``i`` of
  sequence ``b`` from the pool as one ``[block_size, H_kv, d_head]`` tile —
  one DMA per page for all kv heads.  The tile's last two dims are the
  pool's own ``(H_kv, d_head)``, which is what Mosaic's block-shape rule
  asks for;
* all kv heads are scored in one pass: the query tile is flattened to rows
  ``h·Q·G + j`` and the page to columns ``t·H_kv + h'``; one
  ``[H_kv·Q·G, D]·[bs·H_kv, D]ᵀ`` MXU product scores every row against every
  column, and a head mask (``h == h'``) keeps each row on its own kv head.
  The masked columns cost ``H_kv``× the flops of a per-head product — free
  for decode, which is bound by the page DMA, not the MXU;
* pages past a sequence's length — and, under a sliding window, pages wholly
  below it — are skipped via ``pl.when``; partially-valid pages mask by
  absolute position, so stale rows from a block's previous owner are
  invisible;
* int8 pools (the ODIN fixed-8-bit KV working set) dequantize in-kernel:
  the kernel reads half the bytes per page and rescales after the load.

Multi-token queries (``q_len > 1``, speculative verify): the query tile packs
``Q`` in-flight tokens — row ``q·G + g`` of a head's tile sits at absolute
position ``length - Q + q`` and is causally masked against the page axis per
row, so one kernel pass scores a whole draft.  ``q_len == 1`` reduces exactly
to the decode case.

VMEM at phi4-mini widths (``H_kv=8, d_head=128, block_size=16``) and the
widest verify tile (``Q = K+1 = 5`` drafts, ``G = 3`` ⇒ 120 query rows): K/V
tiles 2×32 KB (bf16) double-buffered, q/out 2×30 KB, acc 60 KB, scores
``120×128`` f32 60 KB — under 0.5 MB of the scoped-VMEM budget.
Off the TPU the same kernel runs in the interpreter (tier-1 tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

__all__ = ["paged_attn_kernel", "paged_attn_pallas_call"]

NEG_INF = -1e30


def paged_attn_kernel(lengths_ref, tables_ref, q_ref, k_ref, v_ref, o_ref,
                      m_ref, l_ref, acc_ref, *, block_size: int, n_pages: int,
                      n_kv: int, window: int, scale: float, kv_scale,
                      q_len: int, n_groups: int):
    """One (sequence b, page i) grid step of online-softmax GQA, all heads.

    q_ref [1, H_kv·Q·G, D] · k_ref/v_ref [1, bs, H_kv, D] (page
    ``tables[b, i]`` of the pool) → o_ref [1, H_kv·Q·G, D]; m/l/acc scratch
    carry the softmax state over the page axis.  Row ``h·Q·G + q·G + g`` is
    kv head ``h``, query token ``q`` at absolute position ``length - Q + q``
    (``Q = q_len``; Q == 1 is plain decode).
    """
    b, i = pl.program_id(0), pl.program_id(1)
    length = lengths_ref[b]
    qg = q_len * n_groups
    rows = n_kv * qg
    cols = block_size * n_kv

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Page overlaps the union of the rows' visible ranges?  The last query
    # sits at length-1; the first at length-Q, seeing back to length-Q-window.
    live = i * block_size < length
    if window:
        live = jnp.logical_and(
            live, (i + 1) * block_size > length - q_len - window + 1)

    @pl.when(live)
    def _page():
        q = q_ref[0].astype(jnp.float32)                      # [rows, D]
        # [bs, H_kv, D] → [bs·H_kv, D]: column t·H_kv + h is row t, head h
        k = k_ref[0].astype(jnp.float32).reshape(cols, -1)
        v = v_ref[0].astype(jnp.float32).reshape(cols, -1)
        if kv_scale is not None:                              # int8 pool dequant
            k = k * (1.0 / kv_scale)
            v = v * (1.0 / kv_scale)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [rows, cols]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        pos = i * block_size + col // n_kv
        # per-row causal limit: row h·Q·G + q·G + g is the query at
        # length - Q + q
        q_pos = length - q_len + (row % qg) // n_groups
        ok = jnp.logical_and(pos <= q_pos, col % n_kv == row // qg)
        if window:
            ok = jnp.logical_and(ok, pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # mask p, not just s: a fully-masked row (q_pos < 0, a query tile
        # longer than the sequence) has m_new == NEG_INF and exp(s - m_new)
        # would resurrect every masked column as exp(0) = 1
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == n_pages - 1)
    def _finish():
        # length == 0 (idle slot) leaves l at 0 → output 0, never NaN
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attn_pallas_call(
    q: jax.Array,            # [B, H_kv, Q·G, D] current-token queries
    k_pool: jax.Array,       # [n_blocks, block_size, H_kv, D] physical store
    v_pool: jax.Array,       # [n_blocks, block_size, H_kv, D]
    tables: jax.Array,       # int32 [B, n_pages] pool block ids per slot page
    lengths: jax.Array,      # int32 [B] visible tokens (incl. all Q current)
    *,
    window: int = 0,
    kv_scale=None,           # pool is int8 fixed-point with this scale
    q_len: int = 1,          # Q query tokens packed per sequence
    interpret: bool | None = None,
) -> jax.Array:
    B, Hkv, QG, D = q.shape
    if QG % q_len:
        raise ValueError(f"query tile {QG} not a multiple of q_len {q_len}")
    bs = k_pool.shape[1]
    n_pages = tables.shape[1]
    rows = Hkv * QG
    kernel = functools.partial(
        paged_attn_kernel, block_size=bs, n_pages=n_pages, n_kv=Hkv,
        window=window, scale=1.0 / np.sqrt(D), kv_scale=kv_scale,
        q_len=q_len, n_groups=QG // q_len)
    page = lambda b, i, lens, tabs: (tabs[b, i], 0, 0, 0)
    tile = lambda b, i, lens, tabs: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, rows, D), tile),
            pl.BlockSpec((1, bs, Hkv, D), page),
            pl.BlockSpec((1, bs, Hkv, D), page),
        ],
        out_specs=pl.BlockSpec((1, rows, D), tile),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),     # m: running max
            pltpu.VMEM((rows, 1), jnp.float32),     # l: running denominator
            pltpu.VMEM((rows, D), jnp.float32),     # acc: running numerator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, D), q.dtype),
        interpret=resolve_interpret(interpret),
    )(lengths, tables, q.reshape(B, rows, D), k_pool, v_pool)
    return out.reshape(B, Hkv, QG, D)
