"""The dense GQA decoder family: Phi-3 / Phi-4-mini and their kin.

Pre-norm RMSNorm, rotary embeddings in the half-split form, grouped-query
attention, SwiGLU MLP, residual stream, final RMSNorm, tied or untied LM
head.  A configuration file names this family in ``program.family``; the
harness finds this module by that name and takes three things from it:

* :func:`model_config`: the published keys of ``hf_config`` on the
  program's registry architecture (the one function here that reaches the
  program, through ``harness.program``);
* :func:`forward_rows`: the plain float32 reference, embedding to
  final-normed hidden rows, written from the published architecture.  It
  imports nothing of the program and reads the benchmark's own weights by
  their names in the weight tree;
* the counts the per-layer readers use (:func:`token_flops`,
  :func:`prompt_flops`, :func:`paged_attn_flops`, :func:`paged_attn_bytes`):
  the useful work, whatever implements it: a token's layer work at its own
  context, the LM head only where its logits are used, and for the
  paged-attention kernel only the valid keys and values (rows below the
  slot's length).  Padding, dead pages and recomputation are not counted, so
  a share of a peak computed from these numbers cannot pass 100% unless the
  time leaves out part of the work.

``hf`` is a configuration file's ``hf_config``: the published keys, as run.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.reference import HIGHEST, Q_BLOCK, mm, rms, rope

BF16 = 2

# published key → (where in the program's config, field)
_KEYS = {
    "hidden_size": ("model", "d_model"),
    "vocab_size": ("model", "vocab"),
    "rms_norm_eps": ("model", "norm_eps"),
    "tie_word_embeddings": ("model", "tie_embeddings"),
    "num_hidden_layers": ("block", "n_layers"),
    "intermediate_size": ("block", "d_ff"),
    "num_attention_heads": ("attn", "n_heads"),
    "num_key_value_heads": ("attn", "n_kv_heads"),
    "head_dim": ("attn", "d_head"),
    "rope_theta": ("attn", "rope_theta"),
}


def model_config(config: Dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    architecture named in ``program.arch`` with every published key of
    ``hf_config`` applied.  Keys the program cannot express are refused."""
    from harness.program import base_config

    hf = dict(config["hf_config"])
    hf.setdefault("head_dim", hf["hidden_size"] // hf["num_attention_heads"])
    if hf.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError("the program rotates whole heads only")
    if hf.get("rope_scaling"):
        raise ValueError("the program has no rotary scaling")
    if hf.get("sliding_window"):
        raise ValueError("the program's dense attention has no window")
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's dense MLP here is SwiGLU (silu)")
    cfg = base_config(config["program"]["arch"])
    if len(cfg.blocks) != 1 or cfg.blocks[0].kind != "dense":
        raise ValueError("one dense segment expected")
    model, block, attn = {}, {}, {}
    for key, (where, field) in _KEYS.items():
        {"model": model, "block": block, "attn": attn}[where][field] = hf[key]
    b = cfg.blocks[0]
    b = dataclasses.replace(b, attn=dataclasses.replace(b.attn, **attn),
                            **block)
    return dataclasses.replace(cfg, blocks=(b,), **model)


# ---------------------------------------------------------------- reference

def _dims(hf: Dict) -> Tuple:
    """(heads, kv heads, head size, rotated dims, norm eps, rope theta)."""
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // H
    rot = int(D * hf.get("partial_rotary_factor", 1.0))
    return H, Hkv, D, rot, float(hf["rms_norm_eps"]), float(hf["rope_theta"])


def _layer(x, seg, l, dims, low):
    """One decoder layer on x [n, T, d] (float32), weights of layer ``l``."""
    H, Hkv, D, rot, eps, theta = dims
    w = jax.tree.map(lambda a: a[l].astype(jnp.float32), seg)
    n, T, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (n, T))
    h = rms(x, w["ln1"], eps)
    q = rope(mm(h, w["attn"]["q"], low).reshape(n, T, H, D), pos, theta, rot)
    k = rope(mm(h, w["attn"]["k"], low).reshape(n, T, Hkv, D), pos, theta, rot)
    v = mm(h, w["attn"]["v"], low).reshape(n, T, Hkv, D)
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
    x = x + mm(o, w["attn"]["o"], low)
    h = rms(x, w["ln2"], eps)
    m = w["mlp"]
    g = jax.nn.silu(mm(h, m["w_gate"], low)) * mm(h, m["w_up"], low)
    return x + mm(g, m["w_down"], low)


_layer_jit = jax.jit(_layer, static_argnames=("dims", "low"))


def forward_rows(params, hf, tokens, rows, low):
    """Final-normed hidden rows ``rows`` ([R] flat indices) of the batch
    ``tokens`` [n, T], with every matrix product in ``low`` (None: float32
    at the highest precision)."""
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
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or d // H
    return d, H, Hkv, D, hf["intermediate_size"], hf["num_hidden_layers"]


def layer_matmul_flops(hf: Dict) -> float:
    """Projection and MLP operations of one token through every layer."""
    d, H, Hkv, D, ff, L = _sizes(hf)
    per_layer = 2 * (d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * ff)
    return float(L * per_layer)


def attention_flops(hf: Dict, kv_len: int) -> float:
    """Scores and weighted values of one query row over ``kv_len`` keys,
    every layer (``2·H·D`` for q·k and as much for p·v, per key)."""
    d, H, Hkv, D, ff, L = _sizes(hf)
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
    d, H, Hkv, D, ff, L = _sizes(hf)
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
    K and V rows of every kv head, the query in and the output out, every
    layer."""
    d, H, Hkv, D, ff, L = _sizes(hf)
    return float(L * (2 * kv_len * Hkv * D * kv_bytes + 2 * H * D * BF16))
