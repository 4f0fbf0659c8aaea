"""Fused stochastic-MAC Pallas kernel — ODIN's MAC array on the TPU VPU.

One kernel invocation performs, entirely in VMEM (DESIGN.md §2 "fused in
VMEM" — the headline beyond-paper optimization over ODIN's Compute-Partition
round trips):

    B→S (comparator SNG)  →  bit-parallel AND  →  MUX tree  →  popcount

for one ``[bm, bn]`` output tile against a ``[bk]`` slice of the contraction
axis.  The paper's PCRAM flow writes every intermediate stream back to the
Compute Partition (ANN_MUL: 1R+1W *per 256-bit product*); VMEM residency
removes all of that traffic.

TPU mapping notes
-----------------
* Streams are packed little-endian into ``W = stream_len/32`` uint32 words.
  The bit-parallel PCRAM row ops (PINATUBO double-row activation) become
  VPU bitwise AND/OR over vector registers.
* B→S is *comparator* SNG: bit ``i`` of the stream for value ``v`` is
  ``rank[i] < v``, where ``rank`` is the fixed permutation that defines the
  SRAM LUT contents.  Gathering LUT rows would be a dynamic gather (slow on
  TPU); the comparison form is a broadcast compare + weighted lane reduce,
  which is pure VPU work and produces *bit-identical* streams to the LUT
  (ops.py recovers the rank vector from the LUT so kernel == reference).
* The MUX tree runs ``log2(bk)`` levels of ``(S∧a)∨(S̄∧b)`` with one packed
  half-density select stream per level (the paper's pre-stored S/S' rows).
* Popcount is ``lax.population_count`` + lane sum — the paper's PISO+counter
  without the 256-cycle serialization (a PCRAM artifact, not ported).

Cross-tile accumulation over the K grid axis is *binary* (int32 adds of
per-tile popcounts) — ODIN's own hybrid binary/stochastic philosophy pushed
one level down.  With a single K tile (``bk == K̂``) the kernel is bit-exact
against ``repro.core.stochastic.sc_matmul``'s full MUX tree.

VMEM budget (defaults bm=bn=8, bk=256, W=8):
  sa 64 KB + sw 64 KB + prod 512 KB + cmp staging ≲ 2 MB  « 16 MB/core.
Production lane packing: the ``W=8`` minor axis underfills the 128-lane VPU;
Mosaic re-tiles ``(bn, W) → (8·16, 8)`` supertiles so lanes stay full — the
logical layout here is what the compiler relays out.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

__all__ = ["sc_mac_kernel", "sc_mac_pallas_call"]


def _pack_last32(cmp_bits: jax.Array) -> jax.Array:
    """bool [..., 32] → uint32 [...]: little-endian bit packing via lane dot."""
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (cmp_bits.astype(jnp.uint32) * weights).sum(axis=-1, dtype=jnp.uint32)


def sc_mac_kernel(a_ref, w_ref, ranks_a_ref, ranks_w_ref, selects_ref, out_ref,
                  *, depth: int):
    """One grid step: out[bm, bn] (+)= popcount(MUXtree_bk(AND(SNG(a), SNG(w)))).

    a_ref: int32 [bm, bk]     — quantized activations (0..L-1; 0-padded)
    w_ref: int32 [bk, bn]     — quantized weights
    ranks_*_ref: int32 [W, 32] — SNG permutation ranks (decorrelated pair)
    selects_ref: uint32 [depth_max, W] — per-level MUX select streams
    out_ref: int32 [bm, bn]
    """
    k = pl.program_id(2)

    a = a_ref[...]                                        # [bm, bk]
    w = w_ref[...]                                        # [bk, bn]
    ranks_a = ranks_a_ref[...]                            # [W, 32]
    ranks_w = ranks_w_ref[...]

    # --- B→S: comparator SNG (bit-identical to the SRAM LUT rows) ----------
    # sa[m, kk, w] = pack_j( ranks_a[w, j] < a[m, kk] )
    sa = _pack_last32(a[:, :, None, None] > ranks_a[None, None])      # [bm, bk, W]
    sw = _pack_last32(w[:, :, None, None] > ranks_w[None, None])      # [bk, bn, W]

    # --- bit-parallel AND (ODIN ANN_MUL / PINATUBO double-row read) --------
    prod = sa[:, None, :, :] & jnp.transpose(sw, (1, 0, 2))[None, :, :, :]
    # prod: [bm, bn, bk, W]

    # --- MUX tree (ODIN ANN_ACC chain, balanced) ---------------------------
    x = prod
    for level in range(depth):
        sel = selects_ref[level]                                      # [W] uint32
        x = (sel & x[..., 0::2, :]) | (~sel & x[..., 1::2, :])
    # x: [bm, bn, 1, W]

    # --- popcount (ODIN S_TO_B, parallel) + hybrid binary accumulate -------
    pop = jax.lax.population_count(x[..., 0, :]).astype(jnp.int32).sum(axis=-1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += pop


def sc_mac_pallas_call(
    a: jax.Array,            # int32 [M, K̂]  (padded: M % bm == 0, K̂ % bk == 0)
    w: jax.Array,            # int32 [K̂, N]
    ranks_a: jax.Array,      # int32 [W, 32]
    ranks_w: jax.Array,      # int32 [W, 32]
    selects: jax.Array,      # uint32 [depth_max, W]
    *,
    block_m: int,
    block_n: int,
    block_k: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Launch the kernel over a (M/bm, N/bn, K̂/bk) grid.  Returns int32 [M, N].

    Semantics: ``out = Σ_ktiles popcount(MUXtree_bk(tile products))`` — pop
    units of per-tile ``K̂_t = block_k``.  Single K tile ⇒ exact full tree.
    """
    M, K = a.shape
    _, N = w.shape
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (M, N, K)
    depth = int(np.log2(block_k))
    assert 1 << depth == block_k, f"block_k must be a power of two, got {block_k}"
    assert selects.shape[0] >= depth, (selects.shape, depth)
    n_k = K // block_k

    kernel = functools.partial(sc_mac_kernel, depth=depth)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec(ranks_a.shape, lambda i, j, k: (0, 0)),
            pl.BlockSpec(ranks_w.shape, lambda i, j, k: (0, 0)),
            pl.BlockSpec(selects.shape, lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(a, w, ranks_a, ranks_w, selects)
