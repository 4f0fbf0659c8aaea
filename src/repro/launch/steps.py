"""Step functions: train (grad-accum, AdamW), prefill, decode.

These are the units the dry-run lowers and the drivers jit:

* ``make_train_step``  — microbatched ``lax.scan`` gradient accumulation
  (mean over microbatches), AdamW with int8 moments, cosine LR.  Params,
  optimizer state and batch come in pre-sharded (pjit in_shardings); GSPMD
  inserts the gradient reduce-scatter/all-gathers the roofline analyzes.
* ``make_prefill_step`` — forward-only; builds fresh caches and fills them.
* ``make_decode_step``  — one token against a deep cache (the decode cells).
* ``make_dp_train_step`` — pure-DP variant under ``shard_map`` with the
  int8 stochastic-rounded compressed gradient all-reduce *in the compiled
  graph* (optim/compress.py).  Used by the elastic/compressed driver and
  the 8-device tests; the big pjit path keeps compression at the DP axis.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import BlockConfig, ModelConfig
from repro.models import lm
from repro.nn.attention import POOL_LEAVES, MixedRows, init_paged_cache
from repro.nn.module import ParamSpec
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.compress import compressed_psum

__all__ = [
    "lr_schedule",
    "make_train_step",
    "make_prefill_step",
    "make_decode_step",
    "make_dp_train_step",
    "optimizer_pspecs",
    "init_serving_caches",
    "make_slot_prefill_step",
    "make_serving_decode_step",
    "make_serving_mixed_step",
    "make_serving_decode_guarded",
    "make_serving_decode_horizon",
    "make_serving_spec_horizon",
    "ngram_propose",
    "pageable_block",
    "speculable",
]


def lr_schedule(step, base: float = 3e-4, warmup: int = 100, total: int = 10_000):
    s = step.astype(jnp.float32)
    warm = (s + 1.0) / max(warmup, 1)        # step 0 trains at base/warmup, not 0
    prog = jnp.clip((s - warmup) / max(total - warmup, 1), 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return base * jnp.where(s < warmup, warm, 0.1 + 0.9 * cos)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig = AdamWConfig(),
                    accum: int = 1, base_lr: float = 3e-4,
                    grad_shardings=None, accum_dtype=jnp.float32,
                    warmup: int = 100) -> Callable:
    """(params, opt_state, batch) → (params, opt_state, metrics).

    ``batch`` leaves are [B, ...] when ``accum == 1`` else [accum, B/accum, ...];
    the accumulation loop is a ``lax.scan`` so HLO stays O(1 microbatch).
    ``grad_shardings`` (tree of NamedShardings matching params) pins the
    accumulation carry and the per-microbatch grads — without it the
    partitioner may replicate the buffers (1.6 TB/device at the 405B cell).
    ``accum_dtype``: fp32 is exact; bf16 halves both the carry and the
    per-layer dW reduction payload (§Perf lever for the 405B cell — the
    mean-of-16-microbatches loses <1 bf16 ulp of the per-leaf sum).
    """

    grad_fn = jax.value_and_grad(lm.loss_fn, has_aux=True)

    def _pin(g_tree):
        if grad_shardings is None:
            return g_tree
        return jax.tree.map(jax.lax.with_sharding_constraint, g_tree, grad_shardings)

    def train_step(params, opt_state, batch):
        if accum == 1:
            (loss, metrics), grads = grad_fn(params, batch, cfg)
            grads = _pin(grads)
        else:
            def micro(carry, mb):
                g_acc, l_acc = carry
                (l, m), g = grad_fn(params, mb, cfg)
                # pinning g (not just the carry) pushes the sharding back
                # through the scan-transpose dW accumulation buffers
                g = _pin(g)
                g_acc = _pin(jax.tree.map(lambda a, b: (a + b.astype(accum_dtype)).astype(accum_dtype), g_acc, g))
                return (g_acc, l_acc + l), None

            g0 = _pin(jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params))
            (grads, loss_sum), _ = jax.lax.scan(micro, (g0, jnp.float32(0)), batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) / accum, grads)
            loss = loss_sum / accum
            metrics = {"loss_total": loss}

        lr = lr_schedule(opt_state["step"], base_lr, warmup=warmup)
        new_params, new_opt = adamw_update(grads, params, opt_state, lr, opt_cfg)
        # NB: shape-preserving reduce — vdot/flatten of a 2-D-sharded grad
        # would force a full all-gather per leaf (measured 11 GB/device of
        # replicated fp32 at phi4 scale before this form was used).
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        out_metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return new_params, new_opt, out_metrics

    return train_step


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, max_len: int) -> Callable:
    """(params, batch) → (last_logits, caches): fill caches for S tokens."""

    def prefill_step(params, batch):
        tokens = batch["tokens"]
        B = tokens.shape[0]
        caches = lm.init_caches(cfg, B, max_len)
        logits, caches, _ = lm.forward(
            params, tokens, cfg, caches=caches,
            patch_embeds=batch.get("patch_embeds"), pos3d=batch.get("pos3d"),
        )
        return logits[:, -1], caches

    return prefill_step


def _cache_start(caches):
    """Absolute position of the incoming token(s), from the attn ``pos`` leaf.

    Every attention layer advances its cache position in lockstep, so the
    first segment's layer-0 entry is authoritative.  Returns a scalar (static
    batch), a [B] vector (serving caches), or None (recurrent-only stacks,
    where positions only feed RoPE and there is no RoPE without attention).
    """
    for seg in caches:
        if isinstance(seg, dict) and "attn" in seg and "pos" in seg["attn"]:
            return seg["attn"]["pos"][0]
    return None


def _argmax_tokens(logits, cfg: ModelConfig):
    if cfg.n_codebooks > 1:
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)       # [B, K]
        return nxt[:, :, None]
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)           # [B]
    return nxt[:, None]


def make_decode_step(cfg: ModelConfig) -> Callable:
    """(params, caches, tokens [B,1][, tables]) → (next_tokens [B,1], caches).

    The query position is read from the cache ``pos`` leaf — without it the
    decoded token runs at position 0: wrong RoPE phase AND a causal mask that
    hides every cache row but the first.  ``tables`` (per-slot block tables)
    only matter when the caches carry the paged block pool.
    """

    def decode_step(params, caches, tokens, tables=None):
        start = _cache_start(caches)
        if start is not None and start.ndim:
            start = start[:, None]
        logits, caches, _ = lm.forward(params, tokens, cfg, caches=caches,
                                       start_pos=start, tables=tables)
        return _argmax_tokens(logits, cfg), caches

    return decode_step


# ---------------------------------------------------------------------------
# continuous-batching serving steps (repro.serving)
# ---------------------------------------------------------------------------

def _leaf_name(path) -> str:
    return jax.tree_util.keystr(path[-1:]).strip("[]'\"")


def pageable_block(b: BlockConfig) -> bool:
    """Whether a segment's attention cache can use the paged block pool.

    Non-windowed GQA only: sliding-window layers already hold O(window) ring
    state, and MLA's compressed latent keeps its dense layout (both stay on
    the existing cache-family dispatch).
    """
    return (b.kind in ("dense", "moe", "hymba") and b.attn is not None
            and b.attn.kind == "gqa" and b.attn.window == 0)


def _pool_trash_block(caches) -> Optional[int]:
    """Index of the write-off block of the paged pool (None ⇒ no paged leaves)."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        if _leaf_name(path) in POOL_LEAVES:
            return leaf.shape[1] - 1
    return None


def init_serving_caches(cfg: ModelConfig, batch: int, max_len: int, dtype=None,
                        window_headroom: int = 0, round_to: int = 1,
                        block_size: int = 0, n_blocks: int = 0):
    """Stacked decode caches with *per-slot* position vectors.

    Identical to ``lm.init_caches`` except:

    * attention ``pos`` leaves are [L, B] int32 vectors instead of [L]
      scalars, so each batch slot tracks its own sequence length
      (nn/attention.py takes the batched-scatter write path and builds
      per-slot visibility masks) — every per-slot leaf then carries the slot
      axis at position 1, which is what the slot slice/update helpers rely on;
    * with ``n_blocks > 0``, paged-capable segments (``pageable_block``) get
      the **physical block pool** instead of a dense ``[B, max_len]`` live
      cache: ``k_pool/v_pool [L, n_blocks+1, block_size, H_kv, D]`` shared by
      every slot and addressed through per-slot block tables — device KV
      memory scales with the pool, not ``slots × max_len``;
    * sliding-window ring buffers get ``window_headroom`` extra rows (rounded
      up to ``round_to``, capped at ``max_len``).  A prefill chunk of C
      tokens through a ring of exactly ``window`` rows overwrites keys its
      own early queries still need; ``window + C`` rows keep every key alive
      until every query that may attend to it has run, making chunked prefill
      exact for window attention.  (Masking is position-based, so extra rows
      only cost memory.)
    """
    if dtype is None:
        dtype = jnp.dtype(cfg.kv_dtype)
    override = None
    if n_blocks:
        override = lambda b: (init_paged_cache(b.attn, n_blocks, block_size, dtype)
                              if pageable_block(b) else None)
    caches = lm.init_caches(cfg, batch, max_len, dtype, attn_override=override)

    def fix(path, leaf):
        name = _leaf_name(path)
        if name == "pos":
            return jnp.zeros((*leaf.shape, batch), jnp.int32)
        if window_headroom and name in ("k", "v") and leaf.shape[2] < max_len:
            size = leaf.shape[2] + window_headroom
            size = min(max_len, -(-size // round_to) * round_to)
            if size > leaf.shape[2]:
                pad = [(0, 0)] * leaf.ndim
                pad[2] = (0, size - leaf.shape[2])
                return jnp.pad(leaf, pad)
        return leaf

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
    return jax.tree_util.tree_unflatten(treedef, [fix(p, l) for p, l in flat])


def make_slot_prefill_step(cfg: ModelConfig, max_len: int,
                           window_headroom: int = 0, round_to: int = 1,
                           block_size: int = 0, paged: bool = False) -> Callable:
    """Chunked prefill of ONE batch slot of a serving cache.

    (params, caches, tokens [1,C], slot, start, reset, tables)
        → (last_logits, caches)

    Per-slot leaves are sliced out ([L, 1, ...] per leaf), the chunk runs the
    ordinary forward at absolute positions [start, start+C), and the slices
    are written back.  Paged pool leaves have no slot axis: they pass through
    whole, and the forward **writes the chunk's K/V blocks directly into the
    pool** via the slot's block-table row — there is no dense staging copy.
    ``reset`` (traced bool) restores the slot's per-slot leaves to their true
    initial state first (mLSTM/sLSTM states do not initialize to zeros and
    the slot may hold a previous request's state); pool blocks never need a
    reset because rows at or beyond the slot's ``pos`` are invisible, and the
    rows below it are overwritten by this very prefill.  A reset at
    ``start > 0`` starts the slot *mid-sequence*: ``pos`` leaves reset to
    ``start`` instead of 0, so a tail-only prefill behind a shared resident
    prefix (prefix sharing) writes and attends exactly like the later chunks
    of a full prefill — rows below ``start`` are read through the block
    table, never recomputed.
    ``slot``/``start`` are traced scalars so one executable serves every slot
    and chunk offset; only distinct chunk *lengths* compile separately.
    """

    def prefill_chunk(params, caches, tokens, slot, start, reset, tables=None,
                      patch_embeds=None, pos3d=None):
        flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
        init = init_serving_caches(cfg, 1, max_len,
                                   window_headroom=window_headroom,
                                   round_to=round_to, block_size=block_size,
                                   n_blocks=1 if paged else 0)
        init_flat = [l for _, l in jax.tree_util.tree_flatten_with_path(init)[0]]
        sl = []
        for (path, leaf), ini in zip(flat, init_flat):
            if _leaf_name(path) in POOL_LEAVES:
                sl.append(leaf)                      # shared pool: pass whole
            else:
                s = jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1)
                if _leaf_name(path) == "pos":
                    ini = ini + start                # mid-sequence reset
                sl.append(jnp.where(reset, ini, s))
        sl = jax.tree_util.tree_unflatten(treedef, sl)
        trow = (jax.lax.dynamic_slice_in_dim(tables, slot, 1, axis=0)
                if paged else None)
        logits, sl, _ = lm.forward(params, tokens, cfg, caches=sl,
                                   patch_embeds=patch_embeds, pos3d=pos3d,
                                   start_pos=start, moe_no_drop=True,
                                   tables=trow)
        out = []
        for (path, old), (_, new) in zip(
                flat, jax.tree_util.tree_flatten_with_path(sl)[0]):
            if _leaf_name(path) in POOL_LEAVES:
                out.append(new)                      # updated in place
            else:
                out.append(jax.lax.dynamic_update_slice_in_dim(
                    old, new, slot, axis=1))
        return logits[:, -1], jax.tree_util.tree_unflatten(treedef, out)

    return prefill_chunk


def _sample_tokens(logits, cfg: ModelConfig, key, temperature, top_k: int):
    """Next-token pick: greedy argmax, or temperature + top-k sampling.

    ``key is None`` ⇒ compiled greedy-only path (no sampling ops in the
    graph).  Otherwise per-slot keys are derived by ``fold_in`` so each slot
    draws an independent stream, and a traced ``temperature == 0`` still
    selects the argmax (the engine passes one executable either way).
    """
    last = logits[:, -1]                         # [B, V] or [B, K, V]
    greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
    if key is None:
        nxt = greedy
    else:
        masked = last.astype(jnp.float32)
        if top_k:
            kth = jax.lax.top_k(masked, top_k)[0][..., -1:]
            masked = jnp.where(masked >= kth, masked, -1e30)
        B = last.shape[0]
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(B))
        scaled = masked / jnp.maximum(temperature, 1e-6)
        sampled = jax.vmap(lambda k, l: jax.random.categorical(k, l, axis=-1))(
            keys, scaled).astype(jnp.int32)
        nxt = jnp.where(temperature > 0, sampled, greedy)
    return nxt[:, :, None] if cfg.n_codebooks > 1 else nxt[:, None]


def make_serving_decode_step(cfg: ModelConfig, top_k: int = 0,
                             sample: bool = False) -> Callable:
    """One decode step over all serving slots with an activity mask.

    (params, caches, tokens [B,1], lengths [B], active [B], tables [B,P],
     key, temperature) → (next, caches)

    Inactive slots (free, draining, or mid-admission) still flow through the
    compiled step — the fixed [B, 1] shape is what keeps one executable
    serving every request mix — but their cache updates are discarded: per-
    slot leaves by a select, and paged pool writes by pointing the inactive
    slots' block tables at the pool's write-off block (the pool has no slot
    axis to select over, so masking happens at the write address).
    ``lengths`` must equal the per-slot cache ``pos`` (the scheduler's view
    of each slot's cached length).  ``sample=False`` compiles the pure greedy
    step (key/temperature accepted but unused); ``sample=True`` adds the
    temperature + top-k path of :func:`_sample_tokens`.
    """

    def decode_step(params, caches, tokens, lengths, active, tables=None,
                    key=None, temperature=0.0):
        nxt, caches = _masked_decode(params, caches, tokens, lengths, active,
                                     tables, key if sample else None,
                                     temperature, cfg, top_k)
        return nxt, caches

    return decode_step


def make_serving_mixed_step(cfg: ModelConfig, top_k: int = 0,
                            sample: bool = False) -> Callable:
    """ONE dispatch carrying decode rows AND prefill-chunk rows together.

    (params, caches, dec_tokens [B] (or [B,K]), lane_tokens [L,Q] (or
     [L,K,Q]), lengths [B], decode [B], lane_slot [L], lane_lens [L],
     tables [B,P], key, temperature)
        → (next_tokens, last_logits [B,V] (or [B,K,V]), caches)

    The program runs only the rows the dispatch carries, in two groups
    (``nn.attention.MixedRows``): a **decode group** of one row per slot —
    slot ``b``'s pending token where ``decode[b]``, a pad row elsewhere —
    and ``L`` **prefill lanes** of ``Q`` rows, lane ``l`` carrying
    ``lane_lens[l]`` replay tokens of slot ``lane_slot[l]``, right-aligned.
    Embedding, norms, projections and MLP run on the flat ``B + L·Q`` rows,
    so the weights stream once; attention splits the groups, and the LM
    head runs only on the ``B + L`` rows whose logits are read (every
    decode row and each lane's last row).  Programs are keyed by ``Q``
    alone: ``B`` and ``L`` are fixed per engine.

    ``last_logits[s]`` is slot ``s``'s last real row: the next emitted
    token for a decode slot, the first generated token for a slot whose
    prompt just finished (mid-prompt slots' are discarded by the engine),
    and ``next_tokens`` samples it with :func:`_sample_tokens`.
    ``lengths`` is the per-slot cached length *before* this dispatch (==
    cache ``pos``), which advances by each slot's real rows.  Equality with
    the separate paths is structural, not approximate: the decode group
    makes the decode program's kernel call and each lane the chunked
    prefill's gather+sdpa call, so each emitted token is the argmax/sample
    over *the same floats* the separate prefill/decode dispatches would
    have produced.  Pad rows write to the pool's write-off block.
    ``last_logits`` rides back to the host so the engine can emit first
    tokens of finishing prefills with the same host-side argmax/sampling it
    uses on the separate path.
    """

    def mixed_step(params, caches, dec_tokens, lane_tokens, lengths, decode,
                   lane_slot, lane_lens, tables, key=None, temperature=0.0):
        rows = MixedRows(decode, lane_slot, lane_lens)
        B = decode.shape[0]
        tokens = MixedRows.flat_tokens(dec_tokens, lane_tokens)
        logits, caches, _ = lm.forward(params, tokens, cfg, caches=caches,
                                       start_pos=lengths, moe_no_drop=True,
                                       tables=tables, mixed=rows)
        logits = logits[0]                      # [B + L, V] or [B + L, K, V]
        # every slot's last real row: its decode row, or its lane's last row
        # (empty lanes are dropped)
        dst = jnp.where(lane_lens > 0, lane_slot, B)
        last = logits[:B].at[dst].set(logits[B:], mode="drop")
        nxt = _sample_tokens(last[:, None], cfg, key if sample else None,
                             temperature, top_k)
        return nxt, last, caches

    return mixed_step


def make_serving_decode_guarded(cfg: ModelConfig, top_k: int = 0,
                                sample: bool = False) -> Callable:
    """Single decode step with a per-slot NaN/Inf logit guard (+ optional
    fault injection).

    (params, caches, tokens [B,1], lengths [B], active [B], tables [B,P],
     key, temperature, poison [B]) → (next, bad [B], caches)

    ``bad[s]`` is True when slot ``s``'s final-row logits contain a
    non-finite value — the engine quarantines that request as FAILED and
    discards its token.  ``poison`` injects NaN into the marked slots'
    logits *after* the forward pass (the PCRAM-drift analog at the logit
    seam), so co-batched slots see bit-identical logits to an unguarded
    step and keep their streams.  The argmax/sampling path is unchanged for
    finite rows, so emitted tokens match :func:`make_serving_decode_step`
    exactly; the guard costs one ``isfinite`` reduction per slot, paid only
    by engines that opt into guarded decode.
    """

    def decode_step(params, caches, tokens, lengths, active, tables=None,
                    key=None, temperature=0.0, poison=None):
        trash = _pool_trash_block(caches)
        if tables is not None and trash is not None:
            tables = jnp.where(active[:, None], tables, jnp.int32(trash))
        logits, new_caches, _ = lm.forward(params, tokens, cfg, caches=caches,
                                           start_pos=lengths[:, None],
                                           moe_no_drop=True, tables=tables)
        if poison is not None:
            m = poison.reshape((-1,) + (1,) * (logits.ndim - 1))
            logits = jnp.where(m, jnp.nan, logits)
        last = logits[:, -1]
        bad = ~jnp.all(jnp.isfinite(last.reshape(last.shape[0], -1)), axis=-1)

        def merge(path, old, new):
            if _leaf_name(path) in POOL_LEAVES:
                return new
            m = active.reshape((1, active.shape[0]) + (1,) * (old.ndim - 2))
            return jnp.where(m, new, old)

        caches = jax.tree_util.tree_map_with_path(merge, caches, new_caches)
        nxt = _sample_tokens(logits, cfg, key if sample else None,
                             temperature, top_k)
        return nxt, bad, caches

    return decode_step


def _masked_decode(params, caches, tokens, lengths, active, tables, key,
                   temperature, cfg: ModelConfig, top_k: int):
    """One activity-masked decode over all slots (the shared body of the
    single-step and horizon serving decode).  Returns (next_tokens, caches)."""
    trash = _pool_trash_block(caches)
    if tables is not None and trash is not None:
        tables = jnp.where(active[:, None], tables, jnp.int32(trash))
    logits, new_caches, _ = lm.forward(params, tokens, cfg, caches=caches,
                                       start_pos=lengths[:, None],
                                       moe_no_drop=True, tables=tables)

    def merge(path, old, new):
        if _leaf_name(path) in POOL_LEAVES:
            return new              # inactive writes went to the trash block
        m = active.reshape((1, active.shape[0]) + (1,) * (old.ndim - 2))
        return jnp.where(m, new, old)

    caches = jax.tree_util.tree_map_with_path(merge, caches, new_caches)
    nxt = _sample_tokens(logits, cfg, key, temperature, top_k)
    return nxt, caches


def make_serving_decode_horizon(cfg: ModelConfig, H: int, top_k: int = 0,
                                sample: bool = False) -> Callable:
    """``H`` decode steps fused into ONE compiled dispatch (``lax.scan``).

    (params, caches, tokens [B,1], lengths [B], active [B], remaining [B],
     tables [B,P], key, temperature, step0, eos_id)
        → (token_block [B, H] (or [B, K, H]), counts [B],
           last_tokens [B, 1] (or [B, K, 1]), caches)

    Each inner step runs the same activity-masked decode as
    :func:`make_serving_decode_step` and feeds the sampled/argmaxed token back
    as the next step's input **on-device** — the host pays one dispatch and
    one sync for ``H`` tokens instead of ``H`` of each.  Per-slot freezing
    happens mid-horizon on-device: a slot leaves the activity mask once its
    ``remaining`` generation budget hits zero or it emits ``eos_id``
    (``eos_id < 0`` disables EOS).  Frozen slots keep flowing through the
    fixed-shape forward, but their cache updates are discarded, their lengths
    stop advancing, and their later tokens are not counted.

    ``counts[s]`` is the number of valid tokens for slot ``s`` — because
    freezing is monotone, slot ``s``'s valid tokens are exactly
    ``token_block[s, ..., :counts[s]]``.  ``step0`` is the engine's global
    decode-step counter at horizon entry: inner step ``h`` draws its sampling
    key as ``fold_in(key, step0 + h)``, the same schedule the single-step
    path uses, so a horizon run is token-identical to ``H`` single steps
    (greedy always; sampled whenever the slot schedule matches).
    """

    def horizon_step(params, caches, tokens, lengths, active, remaining,
                     tables=None, key=None, temperature=0.0,
                     step0=0, eos_id=-1):
        B = lengths.shape[0]
        tok_mask_shape = (B,) + (1,) * (tokens.ndim - 1)

        def inner(carry, h):
            caches, tok, lengths, act, rem = carry
            k = jax.random.fold_in(key, step0 + h) if sample else None
            nxt, caches = _masked_decode(params, caches, tok, lengths, act,
                                         tables, k, temperature, cfg, top_k)
            # EOS on the first codebook (single-codebook: the token itself)
            first = nxt.reshape(B, -1)[:, 0]
            hit_eos = (eos_id >= 0) & (first == eos_id)
            rem = rem - act.astype(jnp.int32)
            lengths = lengths + act.astype(jnp.int32)
            new_act = act & (rem > 0) & ~hit_eos
            tok = jnp.where(act.reshape(tok_mask_shape), nxt, tok)
            return (caches, tok, lengths, new_act, rem), (nxt, act)

        (caches, tok, lengths, act, rem), (toks, emitted) = jax.lax.scan(
            inner, (caches, tokens, lengths, active, remaining),
            jnp.arange(H, dtype=jnp.int32))
        counts = emitted.astype(jnp.int32).sum(axis=0)              # [B]
        # toks: [H, B, 1] or [H, B, K, 1] → [B, H] / [B, K, H]
        block = jnp.moveaxis(toks[..., 0], 0, -1)
        return block, counts, tok, caches

    return horizon_step


# ---------------------------------------------------------------------------
# n-gram self-speculative decode (draft-free prompt-lookup verification)
# ---------------------------------------------------------------------------

def speculable(cfg: ModelConfig) -> bool:
    """Whether the config supports n-gram self-speculative serving decode.

    Speculation rolls back rejected KV writes by *not advancing* per-slot
    lengths — sound exactly when every piece of decode state is
    position-addressed (paged pool blocks, dense KV rows, MLA latents: stale
    rows past the length are invisible to every later query).  Recurrent
    state (Hymba's SSM branch, xLSTM cells) advances per token and cannot be
    truncated, and multi-codebook token frames have no scalar n-gram to
    match, so both stay on the plain decode paths.
    """
    return cfg.n_codebooks == 1 and all(
        b.kind in ("dense", "moe") and b.attn is not None for b in cfg.blocks)


def ngram_propose(hist, K: int, n: int = 2):
    """Draft ``K`` tokens per slot by prompt-lookup over the token history.

    ``hist [B, W]`` holds each slot's most recent context tokens
    right-aligned (prompt tail + generated ids, ``-1`` padding on the left).
    The final ``n``-gram is matched against every earlier offset in one
    vectorized comparison; the draft is the ``K`` tokens that followed the
    most recent match — the classic prompt-lookup heuristic, entirely
    on-device (no host round-trip inside the horizon scan).  No match (or a
    match into padding) degenerates to repeating the last token, which the
    verify step simply rejects.
    """
    B, W = hist.shape
    J = W - n - K + 1               # candidate starts; excludes the tail itself
    if J < 1:
        raise ValueError(f"history window {W} too short for n={n}, K={K}")
    tail = hist[:, W - n:]
    m = jnp.ones((B, J), bool)
    for i in range(n):
        m = m & (hist[:, i:i + J] == tail[:, i:i + 1])
    best = jnp.max(jnp.where(m, jnp.arange(J, dtype=jnp.int32), -1), axis=1)
    has = best >= 0
    cols = jnp.maximum(best, 0)[:, None] + n + jnp.arange(K, dtype=jnp.int32)
    draft = jnp.take_along_axis(hist, cols, axis=1)            # [B, K]
    draft = jnp.where(has[:, None], draft, hist[:, -1:])
    return jnp.maximum(draft, 0)    # padding can leak into a boundary draft


def _spec_merge(old_caches, new_caches, active, m):
    """Merge a K+1-token verify forward's cache updates with per-slot
    rollback: ``pos`` leaves advance by the per-slot accepted count ``m``
    (not the K+1 rows the forward wrote — rows past ``pos + m`` hold
    rejected-draft K/V and stay invisible to every later query), pool leaves
    keep their writes (inactive slots wrote to the trash block), and other
    per-slot leaves select by the activity mask."""

    def merge(path, old, new):
        name = _leaf_name(path)
        if name in POOL_LEAVES:
            return new
        if name == "pos":
            return old + m[None, :]             # [L, B] + [1, B]
        mask = active.reshape((1, active.shape[0]) + (1,) * (old.ndim - 2))
        return jnp.where(mask, new, old)

    return jax.tree_util.tree_map_with_path(merge, old_caches, new_caches)


def make_serving_spec_horizon(cfg: ModelConfig, H: int, K: int,
                              n: int = 2) -> Callable:
    """``H`` draft→verify→accept steps fused into ONE compiled dispatch.

    (params, caches, tokens [B,1], lengths [B], active [B], remaining [B],
     hist [B,W], tables [B,P], eos_id)
        → (token_block [B, H, K+1], counts [B, H], last_tokens [B, 1],
           hist, caches)

    Each inner step of the ``lax.scan``:

    1. **draft** — :func:`ngram_propose` reads the slot's on-device token
       history and emits ``K`` draft tokens;
    2. **verify** — ONE forward over ``[pending, d_1..d_K]`` (the
       multi-token-query paged kernel / batched dense decode) yields
       ``K+1`` greedy logits at positions ``len..len+K``;
    3. **accept** — the longest prefix of drafts matching their greedy
       argmax is accepted; the next argmax rides along as the *bonus* token,
       so the step emits ``a+1 ∈ [1, K+1]`` tokens — every one of them an
       argmax of model logits, which is what makes greedy speculation
       token-identical to plain decode by construction;
    4. **rollback** — per-slot lengths advance by the emitted count only
       (clamped by the slot's ``remaining`` budget and a mid-run EOS);
       rejected rows were written into the slot's own pre-extended tail
       blocks and stay invisible, so rollback is a length decrement, never a
       copy;
    5. the bonus/last-emitted token feeds back as the next step's pending
       input and the history ring shifts the emitted run in — all on-device.

    ``counts[s, h]`` is the number of valid tokens in ``token_block[s, h]``
    (0 once the slot froze); freezing is monotone over ``h``.  Greedy only:
    the accept rule compares argmaxes, so there is no sampling path here
    (the engine enforces ``temperature == 0`` for speculation).
    """
    if K < 1:
        raise ValueError(f"spec draft length K must be >= 1, got {K}")

    def spec_step(params, caches, tokens, lengths, active, remaining, hist,
                  tables=None, eos_id=-1):
        B = lengths.shape[0]
        W = hist.shape[1]
        trash = _pool_trash_block(caches)

        def inner(carry, _):
            caches, tok, lengths, act, rem, hist = carry
            draft = ngram_propose(hist, K, n)                   # [B, K]
            tabs = tables
            if tabs is not None and trash is not None:
                tabs = jnp.where(act[:, None], tabs, jnp.int32(trash))
            tin = jnp.concatenate([tok, draft], axis=1)         # [B, K+1]
            logits, new_caches, _ = lm.forward(
                params, tin, cfg, caches=caches, start_pos=lengths[:, None],
                moe_no_drop=True, tables=tabs, spec_decode=True)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [B, K+1]
            # longest accepted draft prefix: d_j must equal the argmax of the
            # logits one position earlier (the token that would have been
            # decoded there)
            match = (draft == g[:, :K]).astype(jnp.int32)
            acc = jnp.cumprod(match, axis=1).sum(axis=1)        # [B] ∈ [0, K]
            is_eos = (eos_id >= 0) & (g == eos_id)
            has_eos = is_eos.any(axis=1)
            eos_cut = jnp.where(has_eos, jnp.argmax(is_eos, axis=1) + 1, K + 1)
            m = jnp.minimum(jnp.minimum(acc + 1, rem), eos_cut)
            m = jnp.where(act, m, 0)                            # emitted count
            caches = _spec_merge(caches, new_caches, act, m)
            lengths = lengths + m
            rem = rem - m
            last = jnp.take_along_axis(g, jnp.maximum(m - 1, 0)[:, None], axis=1)
            tok = jnp.where((m > 0)[:, None], last, tok)
            hit_eos = has_eos & (eos_cut <= m)                  # eos was emitted
            act = act & (rem > 0) & ~hit_eos
            ext = jnp.concatenate([hist, g], axis=1)            # [B, W+K+1]
            hist = jnp.take_along_axis(
                ext, m[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :], axis=1)
            return (caches, tok, lengths, act, rem, hist), (g, m)

        (caches, tok, lengths, act, rem, hist), (toks, counts) = jax.lax.scan(
            inner, (caches, tokens, lengths, active, remaining, hist),
            jnp.arange(H, dtype=jnp.int32))
        # toks: [H, B, K+1] → [B, H, K+1]; counts: [H, B] → [B, H]
        return toks.swapaxes(0, 1), counts.T, tok, hist, caches

    return spec_step


# ---------------------------------------------------------------------------
# sharding trees for optimizer state
# ---------------------------------------------------------------------------

def optimizer_pspecs(param_pspec_tree, opt_cfg: AdamWConfig):
    """PartitionSpec tree matching ``adamw_init``'s structure.

    Moment ``q`` mirrors the param spec; blockwise scales ``s`` replace the
    (possibly sharded) trailing axis with None — scales are tiny.
    """

    def moment(ps: P):
        if opt_cfg.moment_dtype == "float32":
            return {"q": ps}
        entries = list(ps)
        s_spec = P(*entries[:-1], None) if entries else P()
        return {"q": ps, "s": s_spec}

    is_p = lambda x: isinstance(x, P)
    return {
        "mu": jax.tree.map(moment, param_pspec_tree, is_leaf=is_p),
        "nu": jax.tree.map(moment, param_pspec_tree, is_leaf=is_p),
        "step": P(),
    }


# ---------------------------------------------------------------------------
# pure-DP path with real compressed gradient all-reduce (shard_map)
# ---------------------------------------------------------------------------

def make_dp_train_step(cfg: ModelConfig, mesh: Mesh,
                       opt_cfg: AdamWConfig = AdamWConfig(moment_dtype="float32"),
                       base_lr: float = 3e-4, compress: bool = True) -> Callable:
    """Data-parallel train step with int8-compressed gradient all-reduce.

    Params replicated, batch sharded over every mesh axis; each shard
    computes local grads and the cross-shard reduction goes through
    ``compressed_psum`` (int8 payload — 4× fewer wire bytes than fp32,
    visible in the compiled HLO).  This is the honest, compiled realization
    of the paper-adjacent 8-bit theme at the distribution layer.
    """
    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1

    def local(params, opt_state, batch, key):
        (loss, _), grads = jax.value_and_grad(lm.loss_fn, has_aux=True)(params, batch, cfg)
        if compress:
            keys = jax.random.split(key, len(jax.tree.leaves(grads)))
            flat, treedef = jax.tree.flatten(grads)
            flat = [
                compressed_psum(g.astype(jnp.float32).reshape(1, -1), axes, k).reshape(g.shape) / n_shards
                for g, k in zip(flat, keys)
            ]
            grads = jax.tree.unflatten(treedef, flat)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, axes), grads)
        loss = jax.lax.pmean(loss, axes)
        lr = lr_schedule(opt_state["step"], base_lr)
        new_params, new_opt = adamw_update(grads, params, opt_state, lr, opt_cfg)
        return new_params, new_opt, {"loss": loss}

    batch_spec = P(axes)
    rep = P()

    def specs_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def dp_step(params, opt_state, batch, key):
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(specs_like(params, rep), specs_like(opt_state, rep),
                      specs_like(batch, batch_spec), rep),
            out_specs=(specs_like(params, rep), specs_like(opt_state, rep),
                       {"loss": rep}),
            check_vma=False,
        )
        return fn(params, opt_state, batch, key)

    return dp_step
