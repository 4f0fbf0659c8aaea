"""The sparse-expert GQA decoder family: Qwen3-MoE (``model_type`` qwen3_moe).

Pre-norm RMSNorm; grouped-query attention with a per-head RMSNorm of q and
k after the projections and before the rotary embedding (half-split form,
whole heads); in place of the MLP, a mixture of experts: a linear router
(no bias) over every routed expert, softmax in float32, the top
``num_experts_per_tok``, their gates renormalised to sum to 1
(``norm_topk_prob``), each expert a SwiGLU ``down(silu(gate(x)) * up(x))``
and the layer's output the gate-weighted sum; every layer sparse
(``decoder_sparse_step`` 1, no ``mlp_only_layers``), no shared expert;
residual stream, final RMSNorm, untied LM head.  Sources: the Qwen3
Technical Report (arXiv:2505.09388) and the ``config.json`` of
Qwen/Qwen3-235B-A22B.

The expert share.  A deployment divides each layer's experts over the chips
that share it; this chip holds ``num_experts`` of them (experts 0 to
``num_experts - 1``, the first share), while the router keeps its published
width, the file's ``published.num_experts``.  Each token is routed over
every expert and its gates normalised over all of its picks; the layer
here gives the held experts' part of the output, and that partial sum goes
on to the next layer, in the program and in this reference alike.

A configuration file names this family in ``program.family``; the harness
takes from this module :func:`model_config`, :func:`forward_rows` (the
plain float32 reference, which imports nothing of the program and reads the
benchmark's weights by their names in the weight tree) and the counts the
per-layer readers use.  ``hf`` is a configuration file's ``hf_config``: the
published keys, as run.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.reference import HIGHEST, Q_BLOCK, mm, rms, rope
from harness.spec import SpecError

BF16 = 2
# The router's width in the counts below, which the harness hands
# ``hf_config`` alone: Qwen3-MoE's published ``num_experts`` (Qwen3-235B-A22B,
# Qwen3-30B-A3B).  ``model_config`` takes the program's from the file.
ROUTER_EXPERTS = 128

# published key → (where in the program's config, field)
_KEYS = {
    "hidden_size": ("model", "d_model"),
    "vocab_size": ("model", "vocab"),
    "rms_norm_eps": ("model", "norm_eps"),
    "tie_word_embeddings": ("model", "tie_embeddings"),
    "num_hidden_layers": ("block", "n_layers"),
    "num_attention_heads": ("attn", "n_heads"),
    "num_key_value_heads": ("attn", "n_kv_heads"),
    "head_dim": ("attn", "d_head"),
    "rope_theta": ("attn", "rope_theta"),
    "num_experts": ("moe", "n_held"),
    "num_experts_per_tok": ("moe", "top_k"),
    "moe_intermediate_size": ("moe", "d_ff"),
}
# what the program must be able to express, by config class
_NEEDS = {"attn": ("qk_norm",), "moe": ("n_held", "held_start")}


def model_config(config: Dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    architecture named in ``program.arch`` with every published key of
    ``hf_config`` applied, q/k norm on, and the expert share held from
    expert 0.  Keys the program cannot express are refused
    (``ValueError``); a program that has no per-head q/k norm or no expert
    share is refused before anything runs (``SpecError``), so that the model
    never runs without its norm or with its router cut."""
    from harness.program import base_config

    hf = dict(config["hf_config"])
    if not hf.get("norm_topk_prob", False):
        raise ValueError("the program renormalises the top-k gates")
    if hf.get("shared_expert_intermediate_size"):
        raise ValueError("the program's MoE layer here has no shared expert")
    if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
        raise ValueError("every layer is an MoE layer here")
    if hf.get("use_sliding_window") or hf.get("sliding_window"):
        raise ValueError("the program's paged attention has no window")
    if hf.get("rope_scaling"):
        raise ValueError("the program has no rotary scaling")
    if hf.get("attention_bias"):
        raise ValueError("the program's attention projections have no bias")
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's experts are SwiGLU (silu)")
    cfg = base_config(config["program"]["arch"])
    if len(cfg.blocks) != 1 or cfg.blocks[0].kind != "moe":
        raise ValueError("one MoE segment expected")
    b = cfg.blocks[0]
    for part, fields in _NEEDS.items():
        have = {f.name for f in dataclasses.fields(getattr(b, part))}
        if not set(fields) <= have:
            raise SpecError(f"the program's {type(getattr(b, part)).__name__}"
                            f" lacks {sorted(set(fields) - have)}")
    parts = {"model": {}, "block": {}, "attn": {"qk_norm": True},
             "moe": {"n_experts": config["published"]["num_experts"],
                     "held_start": 0, "n_shared": 0}}
    for key, (where, field) in _KEYS.items():
        parts[where][field] = hf[key]
    b = dataclasses.replace(
        b, attn=dataclasses.replace(b.attn, **parts["attn"]),
        moe=dataclasses.replace(b.moe, **parts["moe"]), **parts["block"])
    return dataclasses.replace(cfg, blocks=(b,), **parts["model"])


# ---------------------------------------------------------------- reference

def _dims(hf: Dict) -> Tuple:
    """(heads, kv heads, head size, experts per token, norm eps, theta)."""
    return (hf["num_attention_heads"], hf["num_key_value_heads"],
            hf["head_dim"], hf["num_experts_per_tok"],
            float(hf["rms_norm_eps"]), float(hf["rope_theta"]))


def _attention(h, w, pos, dims, low):
    """Causal GQA with per-head q/k RMSNorm, in blocks of Q_BLOCK queries."""
    H, Hkv, D, _, eps, theta = dims
    n, T, _ = h.shape
    q = mm(h, w["q"], low).reshape(n, T, H, D)
    k = mm(h, w["k"], low).reshape(n, T, Hkv, D)
    v = mm(h, w["v"], low).reshape(n, T, Hkv, D)
    q = rope(rms(q, w["q_norm"], eps), pos, theta, D)
    k = rope(rms(k, w["k_norm"], eps), pos, theta, D)
    G = H // Hkv
    k = jnp.repeat(k, G, axis=2)             # query head j reads kv head j // G
    v = jnp.repeat(v, G, axis=2)
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=1)
        s = jnp.einsum("nqhd,nkhd->nhqk", qb, k, precision=HIGHEST) / np.sqrt(D)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(T // Q_BLOCK))        # [B, n, Qb, H, D]
    o = jnp.moveaxis(o, 0, 1).reshape(n, T, H * D)
    return mm(o, w["o"], low)


def _experts(h, m, k, low):
    """The held experts' part of the MoE layer: route over every expert
    (the router's width), gates of the top ``k`` over their sum, then each
    held expert's SwiGLU on the tokens, weighted by its gate (0 where the
    token did not pick it)."""
    probs = jax.nn.softmax(mm(h, m["router"], low), axis=-1)  # [n, T, E]
    gate, top = jax.lax.top_k(probs, k)
    gate = gate / gate.sum(-1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(m["w_gate"].shape[0]):                     # held: 0..
        g = jnp.sum(jnp.where(top == e, gate, 0.0), axis=-1)[..., None]
        a = jax.nn.silu(mm(h, m["w_gate"][e], low)) * mm(h, m["w_up"][e], low)
        y = y + g * mm(a, m["w_down"][e], low)
    return y


def _layer(x, seg, l, dims, low):
    """One decoder layer on x [n, T, d] (float32), weights of layer ``l``."""
    w = jax.tree.map(lambda a: a[l].astype(jnp.float32), seg)
    n, T, _ = x.shape
    eps = dims[4]
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (n, T))
    x = x + _attention(rms(x, w["ln1"], eps), w["attn"], pos, dims, low)
    return x + _experts(rms(x, w["ln2"], eps), w["moe"], dims[3], low)


_layer_jit = jax.jit(_layer, static_argnames=("dims", "low"))


def forward_rows(params, hf, tokens, rows, low):
    """Final-normed hidden rows ``rows`` ([R] flat indices) of the batch
    ``tokens`` [n, T], with every matrix product (the router's too) in
    ``low`` (None: float32 at the highest precision)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    seg = params["segments"][0]
    dims = _dims(hf)
    for l in range(hf["num_hidden_layers"]):
        x = _layer_jit(x, seg, l, dims=dims, low=low)
    x = x.reshape(-1, x.shape[-1])[rows]
    return rms(x, params["final_norm"].astype(jnp.float32),
               hf["rms_norm_eps"])


# ------------------------------------------------------------------- counts

def _sizes(hf: Dict):
    d = hf["hidden_size"]
    return (d, hf["num_attention_heads"], hf["num_key_value_heads"],
            hf["head_dim"], hf["num_hidden_layers"])


def layer_matmul_flops(hf: Dict) -> float:
    """Projection, router and expert operations of one token through every
    layer.  The expert work is the router's expected share: of its
    ``num_experts_per_tok`` picks among ``ROUTER_EXPERTS``, a token sends
    ``num_experts_per_tok × num_experts / ROUTER_EXPERTS`` to the experts
    held here (8 × 8/128 = 0.5 at the cell's share), each a SwiGLU of
    ``3 · 2 · d · moe_intermediate_size``; the absent experts' work is not
    this chip's."""
    d, H, Hkv, D, L = _sizes(hf)
    share = hf["num_experts_per_tok"] * hf["num_experts"] / ROUTER_EXPERTS
    per_layer = (2 * (d * H * D + 2 * d * Hkv * D + H * D * d)
                 + 2 * d * ROUTER_EXPERTS
                 + share * 3 * 2 * d * hf["moe_intermediate_size"])
    return float(L * per_layer)


def attention_flops(hf: Dict, kv_len: int) -> float:
    """Scores and weighted values of one query row over ``kv_len`` keys,
    every layer (``2·H·D`` for q·k and as much for p·v, per key)."""
    d, H, Hkv, D, L = _sizes(hf)
    return float(L * 4 * H * D * kv_len)


def head_flops(hf: Dict) -> float:
    return float(2 * hf["hidden_size"] * hf["vocab_size"])


def token_flops(hf: Dict, position: int, logits_used: bool) -> float:
    """Model operations of the token at ``position`` (0-based): it attends
    ``position + 1`` keys; the LM head counts only where its logits are
    used."""
    f = layer_matmul_flops(hf) + attention_flops(hf, position + 1)
    return f + (head_flops(hf) if logits_used else 0.0)


def prompt_flops(hf: Dict, prompt_len: int) -> float:
    """Prefill of a whole prompt: every row at its own context, the head on
    the last row only (it gives the first token)."""
    d, H, Hkv, D, L = _sizes(hf)
    n = prompt_len
    keys = n * (n + 1) // 2                     # Σ_{p<n} (p + 1)
    return (n * layer_matmul_flops(hf) + L * 4 * H * D * keys
            + head_flops(hf))


def paged_attn_flops(hf: Dict, kv_len: int) -> float:
    """The paged decode kernel for one query row over ``kv_len`` valid keys,
    every layer."""
    return attention_flops(hf, kv_len)


def paged_attn_bytes(hf: Dict, kv_len: int, kv_bytes: int = BF16) -> float:
    """Bytes the paged decode kernel must move for one query row: the valid
    K and V rows of every kv head (4 of 128 at Qwen3-235B), the query in and
    the output out, every layer."""
    d, H, Hkv, D, L = _sizes(hf)
    return float(L * (2 * kv_len * Hkv * D * kv_bytes + 2 * H * D * BF16))
