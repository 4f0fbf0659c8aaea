"""Decoder LM over heterogeneous scanned layer segments.

Supports every assigned architecture: dense/MoE GQA or MLA transformers,
Hymba hybrids, xLSTM stacks, MusicGen multi-codebook decoding, Qwen2-VL
vision-stub inputs, and DeepSeek MTP.  Params for each segment are stacked
``[n_layers, ...]`` and the stack runs under ``lax.scan`` so HLO size is
O(1 segment) — the 126-layer dry-run cells compile in seconds.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import BlockConfig, ModelConfig
from repro.core.odin_linear import OdinConfig
from repro.nn.blocks import block_apply, block_cache, block_spec
from repro.nn.layers import embed, embed_spec, linear, norm_spec, rmsnorm
from repro.nn.module import ParamSpec, count_params
from repro.nn.pcontext import constrain

__all__ = ["param_spec", "forward", "init_caches", "loss_fn", "model_flops"]

_is_spec = lambda x: isinstance(x, ParamSpec)


def _stack(tree, n: int):
    return jax.tree.map(
        lambda s: ParamSpec((n, *s.shape), ("layers", *s.logical_axes), s.dtype, s.init, s.scale),
        tree, is_leaf=_is_spec,
    )


def _odin(cfg: ModelConfig) -> Optional[OdinConfig]:
    return None if cfg.odin_mode == "exact" else OdinConfig(mode=cfg.odin_mode)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_spec(cfg: ModelConfig) -> Dict:
    spec: Dict = {
        "embed": embed_spec(cfg.vocab, cfg.d_model)
        if cfg.n_codebooks == 1
        else ParamSpec((cfg.n_codebooks, cfg.vocab, cfg.d_model), (None, "vocab", "embed")),
        "final_norm": norm_spec(cfg.d_model),
        "segments": [
            _stack(block_spec(b, cfg.d_model), b.n_layers) for b in cfg.blocks
        ],
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = (
            ParamSpec((cfg.d_model, cfg.vocab), ("embed", "vocab"), init="fan_in")
            if cfg.n_codebooks == 1
            else ParamSpec((cfg.n_codebooks, cfg.d_model, cfg.vocab), (None, "embed", "vocab"), init="fan_in")
        )
    if cfg.mtp:
        spec["mtp"] = {
            "proj": ParamSpec((2 * cfg.d_model, cfg.d_model), ("embed", "embed2"), init="fan_in"),
            "norm": norm_spec(cfg.d_model),
            "block": block_spec(cfg.blocks[0], cfg.d_model),
        }
    return spec


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _segment_apply(params_stacked, x, bcfg: BlockConfig, caches, positions, pos3d,
                   odin, remat: str, norm_eps: float, moe_no_drop: bool = False,
                   tables=None, spec_decode: bool = False, mixed=None):
    """Scan one homogeneous segment of layers over the sequence activations."""
    spec1 = block_spec(bcfg, x.shape[-1])

    def layer(x, inp):
        p, c = inp
        # pin each per-layer param slice to its logical sharding: the scan
        # backward accumulates param cotangents into a stacked [L, ...]
        # buffer whose layout the partitioner copies from these slices —
        # unpinned, it replicates them (1.6 TB/device at the 405B cell).
        p = jax.tree.map(
            lambda w, s: constrain(w, s.logical_axes), p, spec1,
            is_leaf=lambda n: isinstance(n, ParamSpec),
        )
        y, c2 = block_apply(p, x, bcfg, cache=c, positions=positions, pos3d=pos3d,
                            odin=odin, norm_eps=norm_eps, moe_no_drop=moe_no_drop,
                            tables=tables, spec_decode=spec_decode, mixed=mixed)
        # pin the scanned activation sharding so carry propagation never
        # settles on "replicated" (no-op outside a logical_sharding context)
        y = constrain(y, ("batch", "act_seq", None))
        return y, c2

    if remat == "full":
        layer = jax.checkpoint(layer, prevent_cse=False)
    elif remat == "dots":
        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False,
        )
    x, new_caches = jax.lax.scan(layer, x, (params_stacked, caches))
    return x, new_caches


def forward(params, tokens, cfg: ModelConfig, caches=None, patch_embeds=None,
            pos3d=None, start_pos=None, moe_no_drop: bool = False, tables=None,
            spec_decode: bool = False, mixed=None):
    """tokens: [B,S] (or [B,K,S] multi-codebook) → (logits, new_caches).

    logits: [B,S,V] (or [B,S,K,V]).  ``caches``: list of per-segment stacked
    caches (or None for teacher-forced training).  ``start_pos``: absolute
    position of tokens[:, 0] (decode); defaults to 0.  ``moe_no_drop``:
    route without capacity dropping (serving paths — exact, per-token
    deterministic routing; training keeps the capped capacity).  ``tables``:
    per-slot KV block tables [B, n_pages] when the caches carry the paged
    block pool (one table serves every layer; scan-invariant).
    ``spec_decode``: the S tokens are an in-flight speculative draft —
    paged attention runs the multi-token-query decode kernel instead of the
    prefill gather path.  ``mixed``: the row layout
    (``nn.attention.MixedRows``) of a mixed prefill+decode dispatch (paged
    GQA caches only): ``tokens`` are its flat rows ``[1, N]`` (or
    ``[1, K, N]``), ``start_pos`` is the per-slot cached length [B] before
    the dispatch, and logits come back only for the rows that are read,
    ``[1, B + L, V]`` (:meth:`MixedRows.head_rows`).
    """
    odin = _odin(cfg)
    if cfg.n_codebooks > 1:
        # MusicGen: sum the K codebook embeddings per frame
        per = jax.vmap(lambda t, e: jnp.take(e, t, axis=0), in_axes=(1, 0), out_axes=1)(
            tokens, params["embed"]
        )                                                        # [B,K,S,d]
        x = per.sum(axis=1)
    else:
        x = embed(tokens, params["embed"])
    if cfg.vision_stub and patch_embeds is not None:
        # overlay precomputed patch embeddings on the image-token positions
        x = jax.lax.dynamic_update_slice(x, patch_embeds.astype(x.dtype), (0, 0, 0))

    start = jnp.int32(0) if start_pos is None else start_pos
    B, S = x.shape[0], x.shape[1]
    if mixed is not None:
        positions = mixed.positions(start, S)
    else:
        positions = start + jnp.arange(S, dtype=jnp.int32)[None, :] + jnp.zeros((B, 1), jnp.int32)

    new_caches = []
    for i, bcfg in enumerate(cfg.blocks):
        c = caches[i] if caches is not None else None
        if c is None:
            x, _ = _segment_apply(params["segments"][i], x, bcfg, None, positions, pos3d,
                                  odin, cfg.remat, cfg.norm_eps, moe_no_drop)
            new_caches.append(None)
        else:
            x, c2 = _segment_apply(params["segments"][i], x, bcfg, c, positions, pos3d,
                                   odin, cfg.remat, cfg.norm_eps, moe_no_drop,
                                   tables=tables, spec_decode=spec_decode,
                                   mixed=mixed)
            new_caches.append(c2)

    hidden = x
    if mixed is not None:
        x = x[:, mixed.head_rows(S)]      # the head runs on read rows only
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if cfg.n_codebooks > 1:
        logits = jnp.einsum("bsd,kdv->bskv", x, head.astype(x.dtype))
    else:
        logits = jnp.matmul(x, head.astype(x.dtype))
    return logits, (new_caches if caches is not None else None), hidden


def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype=None,
                attn_override=None):
    """Stacked per-segment decode caches (dtype defaults to cfg.kv_dtype).

    ``attn_override(block_cfg) -> dict | None`` substitutes a segment's
    attention cache before stacking (the serving layer swaps in the paged
    block pool this way without materializing the dense layout first).
    """
    if dtype is None:
        dtype = jnp.dtype(cfg.kv_dtype)
    out = []
    for b in cfg.blocks:
        one = block_cache(b, cfg.d_model, batch, max_len, dtype)
        if attn_override is not None:
            sub = attn_override(b)
            if sub is not None:
                one["attn"] = sub
        stacked = jax.tree.map(lambda a: jnp.broadcast_to(a, (b.n_layers, *a.shape)).copy()
                               if hasattr(a, "shape") else a, one)
        out.append(stacked)
    return out


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def _xent(logits, labels, vocab: int):
    """Cross-entropy in the vocab-sharded-friendly form.

    ``take_along_axis`` on a vocab-sharded logits tensor makes GSPMD gather
    the full vocab axis (3.3 GB fp32 per microbatch at phi4's 200k vocab);
    the masked-reduce form keeps every op vocab-local (the label pick and
    the logsumexp both reduce over vocab, which shards as a psum), and its
    gradient (softmax − onehot) stays elementwise-sharded too.
    """
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    picked = jnp.sum(jnp.where(iota == labels[..., None], shifted, 0.0), axis=-1)
    return lse - picked


def loss_fn(params, batch: Dict, cfg: ModelConfig):
    """batch: tokens [B,S]/[B,K,S], labels same shape, optional stubs."""
    tokens, labels = batch["tokens"], batch["labels"]
    logits, _, h = forward(params, tokens, cfg,
                           patch_embeds=batch.get("patch_embeds"), pos3d=batch.get("pos3d"))
    if cfg.n_codebooks > 1:
        loss = _xent(logits, labels.swapaxes(1, 2), cfg.vocab).mean()
    else:
        loss = _xent(logits, labels, cfg.vocab).mean()
    metrics = {"loss": loss}
    if cfg.mtp:
        # Multi-token prediction (DeepSeek-V3): predict t+2 from h_t ++ emb(t+1)
        odin = _odin(cfg)
        x = embed(tokens, params["embed"])
        hm = rmsnorm(h[:, :-1], params["mtp"]["norm"], cfg.norm_eps)
        comb = jnp.concatenate([hm, x[:, 1:]], axis=-1)
        z = jnp.matmul(comb, params["mtp"]["proj"].astype(comb.dtype))
        B, S1 = z.shape[0], z.shape[1]
        pos = jnp.arange(S1, dtype=jnp.int32)[None, :] + jnp.zeros((B, 1), jnp.int32)
        z, _ = block_apply(params["mtp"]["block"], z, cfg.blocks[0], positions=pos,
                           odin=odin, norm_eps=cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        mtp_logits = jnp.matmul(z, head.astype(z.dtype))[:, :-1]   # predicts t+2
        mtp_loss = _xent(mtp_logits, labels[:, 2:] if labels.shape[1] > 2 else labels[:, :0], cfg.vocab).mean()
        metrics["mtp_loss"] = mtp_loss
        loss = loss + 0.1 * mtp_loss
    metrics["loss_total"] = loss
    return loss, metrics


def model_flops(cfg: ModelConfig, n_tokens: int, train: bool = True) -> float:
    """MODEL_FLOPS = 6·N_active·D (roofline §g): params actually touched/token."""
    spec = param_spec(cfg)
    total = count_params(spec)
    # subtract non-active expert params for MoE
    inactive = 0
    for b in cfg.blocks:
        if b.kind == "moe" and b.moe is not None:
            per_expert = 3 * cfg.d_model * b.moe.d_ff
            inactive += b.n_layers * per_expert * (b.moe.n_experts - b.moe.top_k)
    active = total - inactive
    mult = 6.0 if train else 2.0
    return mult * active * n_tokens
