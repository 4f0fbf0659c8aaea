"""Attention: GQA and MLA (DeepSeek), RoPE/M-RoPE, sliding window, KV caches.

Long sequences use a blockwise flash-style scan (online softmax over KV
chunks, O(S·C) live memory instead of O(S²)) — required for the 32k-prefill
cells to fit the dry-run memory budget; short sequences use one einsum.
Decode (S_q = 1) takes a direct GEMV-shaped path against the cache.

Caches:
* GQA: full ``k/v [B, S_max, H_kv, D]`` or, when ``window > 0``, a ring
  buffer of ``window`` entries (Hymba's sliding-window heads ⇒ O(window)
  state for the 500k-context cell).
* Paged GQA (serving): the **physical block pool**
  ``k_pool/v_pool [n_blocks+1, block_size, H_kv, D]`` shared by every slot;
  per-slot block ``tables`` (passed alongside the cache — they are engine
  state, one table for all layers) map logical pages to pool blocks.  Decode
  attends in place via the Pallas paged kernel; device KV memory scales with
  the pool, not ``slots × max_len``.  The last pool block is the write-off
  target for inactive slots (``init_paged_cache``).
* MLA: *compressed* latent ``c_kv [B, S_max, r]`` + shared ``k_rope`` — the
  paper-exact DeepSeek-V3 cache; decompression happens per KV chunk.

The cache ``pos`` is a scalar (static batch: every row advances in lockstep)
or an int32 [B] vector (serving continuous batching: per-slot write offsets
and visibility masks, so one fixed-shape decode serves mixed-length slots).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttnConfig
from repro.core.odin_linear import OdinConfig
from repro.kernels.paged_attn import paged_attention
from repro.nn.layers import apply_mrope, apply_rope, linear, linear_spec, norm_spec, rmsnorm
from repro.nn.module import ParamSpec

__all__ = ["attn_spec", "attention", "init_cache", "init_paged_cache",
           "MixedRows", "DEFAULT_CHUNK", "KV_SCALE", "POOL_LEAVES"]

# Cache-leaf names of the paged physical KV store (block-pool layout); shared
# by the serving step/swap machinery to tell pool leaves (no slot axis) from
# per-slot leaves.
POOL_LEAVES = ("k_pool", "v_pool")

DEFAULT_CHUNK = 512
NEG_INF = -1e30
# int8 KV-cache fixed-point scale: values quantize as round(x·16) ∈ [-127,127]
# (range ±7.94, step 1/16) — the ODIN 8-bit-operand adjustment applied to the
# decode working set.  Post-RoPE K and V magnitudes of trained LMs sit well
# inside ±8 (they are norm-bounded projections); parity tests bound the error.
KV_SCALE = 16.0


def _cache_write(x: jax.Array, cache_dtype) -> jax.Array:
    if cache_dtype == jnp.int8:
        return jnp.clip(jnp.round(x.astype(jnp.float32) * KV_SCALE), -127, 127).astype(jnp.int8)
    return x.astype(cache_dtype)


def _cache_read(x: jax.Array, compute_dtype=jnp.bfloat16) -> jax.Array:
    if x.dtype == jnp.int8:
        return (x.astype(jnp.float32) * (1.0 / KV_SCALE)).astype(compute_dtype)
    return x


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

def attn_spec(cfg: AttnConfig, d_model: int) -> Dict[str, ParamSpec]:
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    if cfg.kind == "mla":
        qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim
        spec = {
            "kv_down": linear_spec(d_model, cfg.kv_lora_rank + cfg.qk_rope_dim, ("embed", None)),
            "kv_norm": ParamSpec((cfg.kv_lora_rank,), (None,), jnp.float32, init="ones"),
            "k_up": linear_spec(cfg.kv_lora_rank, H * cfg.qk_nope_dim, (None, "heads_flat")),
            "v_up": linear_spec(cfg.kv_lora_rank, H * cfg.v_head_dim, (None, "heads_flat")),
            "o": linear_spec(H * cfg.v_head_dim, d_model, ("heads_flat", "embed")),
        }
        if cfg.q_lora_rank:
            spec["q_down"] = linear_spec(d_model, cfg.q_lora_rank, ("embed", None))
            spec["q_norm"] = ParamSpec((cfg.q_lora_rank,), (None,), jnp.float32, init="ones")
            spec["q_up"] = linear_spec(cfg.q_lora_rank, H * qk_dim, (None, "heads_flat"))
        else:
            spec["q"] = linear_spec(d_model, H * qk_dim, ("embed", "heads_flat"))
        return spec
    return {
        "q": linear_spec(d_model, H * D, ("embed", "heads_flat")),
        "k": linear_spec(d_model, Hkv * D, ("embed", "heads_flat")),
        "v": linear_spec(d_model, Hkv * D, ("embed", "heads_flat")),
        "o": linear_spec(H * D, d_model, ("heads_flat", "embed")),
    }


def init_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Abstract-safe cache pytree (works with ShapeDtypeStruct under jit)."""
    if cfg.kind == "mla":
        return {
            "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    size = cfg.window if cfg.window else max_len
    return {
        "k": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.d_head), dtype),
        "v": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.d_head), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def init_paged_cache(cfg: AttnConfig, n_blocks: int, block_size: int,
                     dtype=jnp.bfloat16):
    """Paged physical KV store for one GQA layer (serving continuous batching).

    One device-resident block pool ``[n_blocks+1, block_size, H_kv, D]`` per
    K and V, shared by every serving slot; per-slot block tables (engine
    state, threaded through the compiled steps) map logical pages to pool
    blocks.  Block ``n_blocks`` is the *write-off block*: the decode step
    points inactive slots' tables at it so their writes land somewhere
    harmless without a per-slot select over the (slot-axis-free) pool.
    Batch-independent — slot count is a property of the tables, not the pool.
    """
    if cfg.kind != "gqa" or cfg.window:
        raise ValueError("paged cache supports non-windowed GQA only")
    shape = (n_blocks + 1, block_size, cfg.n_kv_heads, cfg.d_head)
    return {
        "k_pool": jnp.zeros(shape, dtype),
        "v_pool": jnp.zeros(shape, dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# softmax attention cores
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, window: int):
    """[.., Sq, Sk] additive bias: causal + optional sliding window."""
    ok = k_pos[..., None, :] <= q_pos[..., :, None]
    if window:
        ok &= k_pos[..., None, :] > q_pos[..., :, None] - window
    return jnp.where(ok, 0.0, NEG_INF)


def _sdpa(q, k, v, bias, scale):
    """q: [B,Sq,H,D] k/v: [B,Sk,Hkv,Dk/Dv] bias: [B,1,Sq,Sk] or [1,1,Sq,Sk]."""
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32)) * scale
    s = s + bias[:, :, None, :, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def _blockwise(q, k, v, q_pos, k_pos, window: int, scale: float, chunk: int):
    """Flash-style double loop: outer over Q chunks, inner scan over KV chunks.

    ``q_pos``/``k_pos`` are normalized to [B, S] so training (shared causal
    positions), prefill-into-cache and ring-buffer decode all take this path.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    Dv = v.shape[-1]
    Hkv = k.shape[2]
    G = H // Hkv
    cq = min(chunk, Sq)
    ck = min(chunk, Sk)
    nq, nk = Sq // cq, Sk // ck
    assert Sq % cq == 0 and Sk % ck == 0, (Sq, Sk, chunk)

    q_pos = jnp.broadcast_to(q_pos, (B, Sq)) if q_pos.ndim < 2 else q_pos
    k_pos = jnp.broadcast_to(k_pos, (B, Sk)) if k_pos.ndim < 2 else k_pos

    qc = q.reshape(B, nq, cq, Hkv, G, D)
    qpc = q_pos.reshape(B, nq, cq)
    kc = k.reshape(B, nk, ck, Hkv, D)
    vc = v.reshape(B, nk, ck, Hkv, Dv)
    kpc = k_pos.reshape(B, nk, ck)

    def q_block(qi, qp):
        # qi: [B, cq, Hkv, G, D]; qp: [B, cq]; online softmax over kv chunks
        def kv_step(carry, inp):
            m, l, acc = carry
            ki, vi, kp = inp                           # [B,ck,Hkv,D], [B,ck]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qi.astype(jnp.float32), ki.astype(jnp.float32)) * scale
            bias = _mask_bias(qp, kp, window)          # [B, cq, ck]
            s = s + bias[:, None, None]
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vi.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, cq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, cq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1), kpc.swapaxes(0, 1)),
        )
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(o, 3, 1).astype(q.dtype)   # [B, cq, Hkv, G, Dv]

    out = jax.lax.map(lambda t: q_block(t[0], t[1]), (qc.swapaxes(0, 1), qpc.swapaxes(0, 1)))
    out = out.swapaxes(0, 1).reshape(B, Sq, H, Dv)
    return out


def sdpa(q, k, v, q_pos, k_pos, window: int = 0, chunk: int = DEFAULT_CHUNK,
         blockwise_threshold: int = 4096):
    """Dispatch between direct and blockwise attention. Shapes as in _sdpa."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    B = q.shape[0]
    Sq, Sk = q.shape[1], k.shape[1]
    if Sq == 1 or (Sq * Sk) <= blockwise_threshold ** 2:
        qp = q_pos if q_pos.ndim == 2 else jnp.broadcast_to(q_pos, (B, Sq))
        kp = jnp.broadcast_to(k_pos, (B, Sk)) if k_pos.ndim == 1 else k_pos
        bias = _mask_bias(qp, kp, window)[:, None]     # [B,1,Sq,Sk]
        return _sdpa(q, k, v, bias, scale)
    # blockwise: pad both sequence axes to the chunk size.  Padded K rows get
    # position 2^30 (causally invisible to every real query); padded Q rows
    # get 2^29 (see everything real, row results are sliced away).
    pq = (-Sq) % min(chunk, max(Sq, 1))
    pk = (-Sk) % min(chunk, max(Sk, 1))
    if pq or pk:
        qp = q_pos if q_pos.ndim == 2 else jnp.broadcast_to(q_pos, (B, Sq))
        kp = k_pos if k_pos.ndim == 2 else jnp.broadcast_to(k_pos, (B, Sk))
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        qp = jnp.pad(qp, ((0, 0), (0, pq)), constant_values=2**29)
        kp = jnp.pad(kp, ((0, 0), (0, pk)), constant_values=2**30)
        out = _blockwise(q, k, v, qp, kp, window, scale, chunk)
        return out[:, :Sq]
    return _blockwise(q, k, v, q_pos, k_pos, window, scale, chunk)


# ---------------------------------------------------------------------------
# full attention blocks (projection + rope + cache + core + output)
# ---------------------------------------------------------------------------

def _positions(batch: int, start, seq: int):
    return start + jnp.arange(seq, dtype=jnp.int32)[None, :] + jnp.zeros((batch, 1), jnp.int32)


class MixedRows(NamedTuple):
    """Row layout of one mixed prefill+decode dispatch (paged GQA caches).

    The dispatch's activations are one flat row axis ``[1, B + L·Q]``:

    * rows ``0 … B-1`` are the **decode group**, one row per slot — slot
      ``b``'s pending token when ``decode[b]``, else a pad row;
    * then ``L`` **prefill lanes** of ``Q`` rows: lane ``l`` carries
      ``lane_lens[l]`` replay tokens of slot ``lane_slot[l]``, right-aligned
      (its last real row is the lane's last row), pad rows before them.  An
      empty lane has ``lane_lens = 0``.

    Everything but attention (embedding, norms, projections, MLP, drop-free
    MoE) runs on the flat rows, so weights stream once for the whole
    dispatch; :func:`_paged_mixed` splits the rows back into the groups.
    A slot is in at most one group per dispatch.
    """
    decode: jax.Array       # [B] bool
    lane_slot: jax.Array    # [L] int32
    lane_lens: jax.Array    # [L] int32

    @staticmethod
    def flat_tokens(dec_tokens, lane_tokens) -> jax.Array:
        """The flat rows ``[1, N]`` (or ``[1, K, N]``) from the decode
        group's tokens ``[B]`` (or ``[B, K]``) and the lanes' ``[L, Q]``
        (or ``[L, K, Q]``)."""
        if lane_tokens.ndim == 3:                        # K codebooks
            L, K, Q = lane_tokens.shape
            lanes = lane_tokens.transpose(1, 0, 2).reshape(K, L * Q)
            return jnp.concatenate([dec_tokens.T, lanes], axis=1)[None]
        return jnp.concatenate([dec_tokens, lane_tokens.reshape(-1)])[None]

    def _dims(self, n_rows: int):
        B, L = self.decode.shape[0], self.lane_slot.shape[0]
        return B, L, (n_rows - B) // L

    def slot_rows(self) -> jax.Array:
        """[B] real rows each slot carries (its cache ``pos`` advance)."""
        return self.decode.astype(jnp.int32).at[self.lane_slot].add(
            self.lane_lens)

    def real(self, n_rows: int) -> jax.Array:
        """[N] bool: which flat rows carry a token."""
        B, L, Q = self._dims(n_rows)
        lane = jnp.arange(Q)[None, :] >= (Q - self.lane_lens)[:, None]
        return jnp.concatenate([self.decode, lane.reshape(-1)])

    def positions(self, lengths, n_rows: int) -> jax.Array:
        """[1, N] absolute position of every flat row, from the per-slot
        cached ``lengths`` [B] before the dispatch (pad rows of a lane get
        earlier, possibly negative, positions: invisible keys, discarded)."""
        B, L, Q = self._dims(n_rows)
        lane = (lengths[self.lane_slot][:, None] + jnp.arange(Q)[None, :]
                - (Q - self.lane_lens)[:, None])
        return jnp.concatenate([lengths, lane.reshape(-1)])[None, :]

    def slots(self, n_rows: int) -> jax.Array:
        """[N] the slot whose table each flat row writes through."""
        B, L, Q = self._dims(n_rows)
        return jnp.concatenate([jnp.arange(B, dtype=jnp.int32),
                                jnp.repeat(self.lane_slot, Q)])

    def head_rows(self, n_rows: int) -> jax.Array:
        """[B + L] the rows whose logits are read: every decode-group row,
        then each lane's last row."""
        B, L, Q = self._dims(n_rows)
        return jnp.concatenate([jnp.arange(B, dtype=jnp.int32),
                                B + Q * jnp.arange(L, dtype=jnp.int32) + Q - 1])


def _paged_mixed(q, k, v, cfg: AttnConfig, positions, cache, tables,
                 rows: MixedRows):
    """Mixed dispatch through the block pool: per-row K/V writes, then the
    decode group through the Pallas kernel and each lane through the
    chunked-prefill gather+sdpa core (see :class:`MixedRows`).

    Each group makes exactly the call its dedicated path makes — the decode
    group the decode program's ``[slots]`` kernel call, each lane the
    chunked prefill's ``[1, c]`` gather over one slot's table, masked at its
    new length — so greedy mixed-on streams stay token-for-token equal to
    mixed-off (the two cores round differently; neither may stand in for
    the other).  Pad rows write to the pool's write-off block, and every
    key stays invisible to them.
    """
    N = q.shape[1]
    B, L, Q = rows._dims(N)
    P = tables.shape[1]
    pos = cache["pos"]
    kp, vp = cache["k_pool"], cache["v_pool"]
    cdt = kp.dtype
    bs, Hkv, D = kp.shape[1], kp.shape[2], kp.shape[3]
    trash = jnp.int32(kp.shape[0] - 1)
    row_pos = positions[0]
    real = rows.real(N)
    page = jnp.where(real, row_pos // bs, jnp.int32(P))
    bids = tables[rows.slots(N), jnp.minimum(page, P - 1)]
    bids = jnp.where(page >= P, trash, bids)
    at = jnp.where(real, row_pos % bs, 0)
    kp = kp.at[bids, at].set(_cache_write(k[0], cdt))
    vp = vp.at[bids, at].set(_cache_write(v[0], cdt))
    new_len = pos + rows.slot_rows()
    new_cache = {"k_pool": kp, "v_pool": vp, "pos": new_len}
    kv_scale = KV_SCALE if cdt == jnp.int8 else None
    # decode group: the decode program's kernel call (tables of slots that
    # are not decoding point at the write-off block, as they do there)
    dec_tables = jnp.where(rows.decode[:, None], tables, trash)
    od = paged_attention(q[0, :B], kp, vp, dec_tables, new_len,
                         window=cfg.window, kv_scale=kv_scale)
    od = jnp.where(rows.decode[:, None, None], od, 0)
    # prefill lanes: the chunked prefill's gather of one slot's pages, keys
    # masked at the slot's new length, sdpa
    lane_tables = tables[rows.lane_slot]                          # [L, P]
    ck = _cache_read(kp[lane_tables].reshape(L, P * bs, Hkv, D), q.dtype)
    cv = _cache_read(vp[lane_tables].reshape(L, P * bs, Hkv, D), q.dtype)
    slot_rows = jnp.arange(P * bs, dtype=jnp.int32)[None, :]
    k_pos = jnp.where(slot_rows < new_len[rows.lane_slot][:, None], slot_rows,
                      jnp.int32(2**30))
    ol = sdpa(q[0, B:].reshape(L, Q, -1, D), ck, cv,
              row_pos[B:].reshape(L, Q), k_pos, cfg.window)
    o = jnp.concatenate([od, ol.reshape(L * Q, -1, D)])[None]
    return o, new_cache


def _paged_gqa_core(q, k, v, cfg: AttnConfig, positions, cache, tables,
                    spec_decode: bool = False):
    """Write the new K/V rows into the block pool and attend through it.

    ``pos`` must be a per-slot [B] vector (paged caches exist only in the
    serving layout); ``tables [B, P]`` maps each slot's logical pages to pool
    blocks.  Decode (S == 1) runs the Pallas paged kernel — K/V blocks are
    read in place from the pool; chunked prefill (S > 1) gathers the table's
    pages once and reuses the blockwise/direct sdpa core (prefill is not the
    per-token hot path, and its cost is O(max_len) regardless).  A
    speculative verify (``spec_decode``, small S = draft+1) keeps the kernel
    path with an S-row query tile instead — per-token decode semantics, no
    O(max_len) gather in the per-dispatch hot loop.  A mixed dispatch — a
    ``[slots, 1]`` decode group plus ``[1, Q]`` prefill lanes — takes
    :func:`_paged_mixed`, which makes this function's S == 1 kernel call
    for the decode group and its chunked-prefill gather+sdpa per lane.

    Writes for rows at or past the table's page span (a verify tile near a
    slot's ``max_len``, where rejected draft rows may overhang the budget)
    are redirected to the pool's write-off block — reading a stale table
    entry there could alias another slot's live block.
    """
    B, S = q.shape[0], q.shape[1]
    P = tables.shape[1]
    pos = cache["pos"]
    kp, vp = cache["k_pool"], cache["v_pool"]
    cdt = kp.dtype
    bs = kp.shape[1]
    rows = pos[:, None] + jnp.arange(S, dtype=jnp.int32)           # [B, S]
    page = rows // bs
    bids = jnp.take_along_axis(tables, jnp.minimum(page, P - 1), axis=1)
    bids = jnp.where(page >= P, jnp.int32(kp.shape[0] - 1), bids)  # [B, S]
    kp = kp.at[bids, rows % bs].set(_cache_write(k, cdt))
    vp = vp.at[bids, rows % bs].set(_cache_write(v, cdt))
    new_cache = {"k_pool": kp, "v_pool": vp, "pos": pos + S}
    kv_scale = KV_SCALE if cdt == jnp.int8 else None
    if S == 1:
        o = paged_attention(q[:, 0], kp, vp, tables, pos + 1,
                            window=cfg.window, kv_scale=kv_scale)[:, None]
    elif spec_decode:
        o = paged_attention(q, kp, vp, tables, pos + S,
                            window=cfg.window, kv_scale=kv_scale)
    else:
        P = tables.shape[1]
        Hkv, D = kp.shape[2], kp.shape[3]
        ck = _cache_read(kp[tables].reshape(B, P * bs, Hkv, D), q.dtype)
        cv = _cache_read(vp[tables].reshape(B, P * bs, Hkv, D), q.dtype)
        slot_rows = jnp.arange(P * bs, dtype=jnp.int32)[None, :]
        k_pos = jnp.where(slot_rows < (pos + S)[:, None], slot_rows,
                          jnp.int32(2**30))
        o = sdpa(q, ck, cv, positions, k_pos, cfg.window)
    return o, new_cache


def _gqa_attention(p, x, cfg: AttnConfig, positions, pos3d, cache, odin,
                   tables=None, spec_decode: bool = False, mixed=None):
    B, S, _ = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = linear(x, p["q"], odin).reshape(B, S, H, D)
    k = linear(x, p["k"], odin).reshape(B, S, Hkv, D)
    v = linear(x, p["v"], odin).reshape(B, S, Hkv, D)
    if cfg.rope == "mrope":
        if pos3d is None:
            # text-only / decode steps: M-RoPE degenerates to (t, t, t)
            pos3d = jnp.broadcast_to(positions[..., None], (B, S, 3))
        q = apply_mrope(q, pos3d, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, pos3d, cfg.mrope_sections, cfg.rope_theta)
    elif cfg.rope == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        k_pos = positions
        o = sdpa(q, k, v, positions, k_pos, cfg.window)
        new_cache = None
    elif "k_pool" in cache:
        if tables is None:
            raise ValueError("paged attention cache requires block tables")
        if mixed is not None:
            o, new_cache = _paged_mixed(q, k, v, cfg, positions, cache, tables,
                                        mixed)
        else:
            o, new_cache = _paged_gqa_core(q, k, v, cfg, positions, cache,
                                           tables, spec_decode=spec_decode)
    else:
        pos = cache["pos"]
        size = cache["k"].shape[1]
        cdt = cache["k"].dtype
        if pos.ndim:
            # per-slot positions (serving continuous batching): pos [B].
            # Batched scatter replaces the scalar dynamic_update_slice; the
            # visibility mask is per-slot so stale rows from a previous slot
            # occupant are invisible to the new request.
            bidx = jnp.arange(B)[:, None]
            rows = pos[:, None] + jnp.arange(S, dtype=jnp.int32)       # [B, S]
            if cfg.window:
                idx = rows % size
                ck = cache["k"].at[bidx, idx].set(_cache_write(k, cdt))
                cv = cache["v"].at[bidx, idx].set(_cache_write(v, cdt))
                k_pos = _ring_positions((pos + S)[:, None], size)       # [B, size]
            else:
                ck = cache["k"].at[bidx, rows].set(_cache_write(k, cdt))
                cv = cache["v"].at[bidx, rows].set(_cache_write(v, cdt))
                slot_rows = jnp.arange(size, dtype=jnp.int32)[None, :]
                k_pos = jnp.where(slot_rows < (pos + S)[:, None], slot_rows, jnp.int32(2**30))
            new_cache = {"k": ck, "v": cv, "pos": pos + S}
            o = sdpa(q, _cache_read(ck, q.dtype), _cache_read(cv, q.dtype),
                     positions, k_pos, cfg.window)
        elif cfg.window:
            idx = (pos + jnp.arange(S)) % size
            ck = cache["k"].at[:, idx].set(_cache_write(k, cdt))
            cv = cache["v"].at[:, idx].set(_cache_write(v, cdt))
            k_pos = _ring_positions(pos + S, size)
            new_cache = {"k": ck, "v": cv, "pos": pos + S}
            o = sdpa(q, _cache_read(ck, q.dtype), _cache_read(cv, q.dtype),
                     positions, jnp.broadcast_to(k_pos, (B, size)), cfg.window)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], _cache_write(k, cdt), pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], _cache_write(v, cdt), pos, axis=1)
            size = ck.shape[1]
            k_pos = jnp.arange(size, dtype=jnp.int32)
            # entries beyond pos+S are zeros — mask them via position > current
            k_pos = jnp.where(k_pos < pos + S, k_pos, jnp.int32(2**30))
            new_cache = {"k": ck, "v": cv, "pos": pos + S}
            o = sdpa(q, _cache_read(ck, q.dtype), _cache_read(cv, q.dtype),
                     positions, jnp.broadcast_to(k_pos, (B, size)), cfg.window)
    o = o.reshape(B, S, H * D)
    return linear(o, p["o"], odin), new_cache


def _ring_positions(next_pos, size: int):
    """Absolute position of each ring-buffer slot given ``next_pos`` total written."""
    slots = jnp.arange(size, dtype=jnp.int32)
    wrapped = next_pos - 1 - (next_pos - 1 - slots) % size
    return jnp.where(slots < next_pos, wrapped, jnp.int32(2**30))


def _mla_attention(p, x, cfg: AttnConfig, positions, cache, odin):
    """DeepSeek-V3 multi-head latent attention with compressed KV cache."""
    B, S, _ = x.shape
    H = cfg.n_heads
    qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim

    if "q_down" in p:
        cq = rmsnorm(linear(x, p["q_down"], odin), p["q_norm"])
        q = linear(cq, p["q_up"], odin).reshape(B, S, H, qk_dim)
    else:
        q = linear(x, p["q"], odin).reshape(B, S, H, qk_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = linear(x, p["kv_down"], odin)
    c_kv, k_rope = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(c_kv, p["kv_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    if cache is not None:
        pos = cache["pos"]
        cdt = cache["c_kv"].dtype
        if pos.ndim:
            # per-slot positions (serving continuous batching): pos [B]
            bidx = jnp.arange(B)[:, None]
            rows = pos[:, None] + jnp.arange(S, dtype=jnp.int32)
            c_kv_q = cache["c_kv"].at[bidx, rows].set(_cache_write(c_kv, cdt))
            k_rope_q = cache["k_rope"].at[bidx, rows].set(_cache_write(k_rope, cdt))
            Sk = c_kv_q.shape[1]
            slot_rows = jnp.arange(Sk, dtype=jnp.int32)[None, :]
            k_pos = jnp.where(slot_rows < (pos + S)[:, None], slot_rows, jnp.int32(2**30))
        else:
            c_kv_q = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], _cache_write(c_kv, cdt), pos, axis=1)
            k_rope_q = jax.lax.dynamic_update_slice_in_dim(cache["k_rope"], _cache_write(k_rope, cdt), pos, axis=1)
            Sk = c_kv_q.shape[1]
            k_pos = jnp.arange(Sk, dtype=jnp.int32)
            k_pos = jnp.where(k_pos < pos + S, k_pos, jnp.int32(2**30))
            k_pos = jnp.broadcast_to(k_pos, (B, Sk))
        new_cache = {"c_kv": c_kv_q, "k_rope": k_rope_q, "pos": pos + S}
        c_kv = _cache_read(c_kv_q, x.dtype)
        k_rope = _cache_read(k_rope_q, x.dtype)
    else:
        new_cache = None
        k_pos = positions

    # decompress latent → per-head K_nope, V (chunk-local inside blockwise core
    # would be cheaper; baseline decompresses once — hillclimb lever)
    k_nope = linear(c_kv, p["k_up"], odin).reshape(B, -1, H, cfg.qk_nope_dim)
    v = linear(c_kv, p["v_up"], odin).reshape(B, -1, H, cfg.v_head_dim)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (*k_nope.shape[:3], cfg.qk_rope_dim))], axis=-1)
    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = sdpa(qfull, k, v, positions, k_pos, cfg.window)
    o = o.reshape(B, S, H * cfg.v_head_dim)
    return linear(o, p["o"], odin), new_cache


def attention(p, x, cfg: AttnConfig, positions=None, pos3d=None, cache=None,
              odin: Optional[OdinConfig] = None, tables=None,
              spec_decode: bool = False, mixed: Optional[MixedRows] = None):
    """Returns (output [B,S,d_model], new_cache).  ``tables`` are the per-slot
    block tables of the paged serving cache (ignored by dense/MLA caches).
    ``spec_decode``: the S tokens are an in-flight speculative draft — paged
    caches attend through the multi-token-query kernel instead of the prefill
    gather (dense/MLA caches already handle S > 1 with decode semantics).
    ``mixed``: the row layout of a mixed prefill+decode dispatch, whose
    rows are flat in ``x [1, N, d]`` (paged GQA caches only; ``positions``
    then come from :meth:`MixedRows.positions`) — see :class:`MixedRows`."""
    B, S, _ = x.shape
    if mixed is not None and (cache is None or "k_pool" not in cache):
        raise ValueError("mixed dispatch requires a paged GQA cache")
    if positions is None:
        start = cache["pos"] if cache is not None else jnp.int32(0)
        if getattr(start, "ndim", 0) == 1:      # per-slot positions [B]
            start = start[:, None]
        positions = _positions(B, start, S)
    if cfg.kind == "mla":
        return _mla_attention(p, x, cfg, positions, cache, odin)
    return _gqa_attention(p, x, cfg, positions, pos3d, cache, odin, tables,
                          spec_decode=spec_decode, mixed=mixed)
