"""Transformer-family blocks: dense, MoE, Hymba (parallel attn ∥ SSM), xLSTM.

Each block kind provides ``*_spec`` (ParamSpec tree) and an apply function
``(params, x, cache) → (x', cache')``.  Blocks are homogeneous within a
segment so the layer stack scans (models/lm.py).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import BlockConfig
from repro.core.odin_linear import OdinConfig
from repro.nn.attention import attention, attn_spec, init_cache
from repro.nn.layers import linear, linear_spec, norm_spec, rmsnorm
from repro.nn.module import ParamSpec
from repro.nn.moe import moe_block, moe_spec
from repro.nn.ssm import init_ssm_state, ssm_block, ssm_spec
from repro.nn.xlstm import (
    init_mlstm_state, init_slstm_state, mlstm_block, mlstm_spec, slstm_block, slstm_spec,
)

__all__ = ["block_spec", "block_apply", "block_cache"]


def _mlp_spec(d_model: int, d_ff: int, activation: str) -> Dict[str, ParamSpec]:
    if activation == "swiglu":
        return {
            "w_gate": linear_spec(d_model, d_ff, ("embed", "mlp")),
            "w_up": linear_spec(d_model, d_ff, ("embed", "mlp")),
            "w_down": linear_spec(d_ff, d_model, ("mlp", "embed")),
        }
    return {
        "w_up": linear_spec(d_model, d_ff, ("embed", "mlp")),
        "w_down": linear_spec(d_ff, d_model, ("mlp", "embed")),
    }


def _mlp(p, x, activation: str, odin):
    if activation == "swiglu":
        h = jax.nn.silu(linear(x, p["w_gate"], odin)) * linear(x, p["w_up"], odin)
    elif activation == "relu2":
        r = jax.nn.relu(linear(x, p["w_up"], odin))
        h = r * r
    else:
        h = jax.nn.gelu(linear(x, p["w_up"], odin))
    return linear(h, p["w_down"], odin)


def block_spec(cfg: BlockConfig, d_model: int) -> Dict:
    if cfg.kind in ("dense", "moe"):
        spec = {
            "ln1": norm_spec(d_model),
            "ln2": norm_spec(d_model),
            "attn": attn_spec(cfg.attn, d_model),
        }
        if cfg.kind == "dense":
            spec["mlp"] = _mlp_spec(d_model, cfg.d_ff, cfg.activation)
        else:
            spec["moe"] = moe_spec(cfg.moe, d_model)
        return spec
    if cfg.kind == "hymba":
        return {
            "ln1": norm_spec(d_model),
            "ln2": norm_spec(d_model),
            "attn": attn_spec(cfg.attn, d_model),
            "ssm": ssm_spec(cfg.ssm, d_model),
            "attn_out_norm": norm_spec(d_model),
            "ssm_out_norm": norm_spec(d_model),
            "mix_beta": ParamSpec((2, d_model), (None, "embed"), jnp.float32, init="ones"),
            "mlp": _mlp_spec(d_model, cfg.d_ff, cfg.activation),
        }
    if cfg.kind == "mlstm":
        return {"ln1": norm_spec(d_model), "cell": mlstm_spec(cfg.attn.n_heads, d_model)}
    if cfg.kind == "slstm":
        return {"ln1": norm_spec(d_model), "cell": slstm_spec(cfg.attn.n_heads, d_model)}
    raise ValueError(cfg.kind)


def block_cache(cfg: BlockConfig, d_model: int, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Per-layer decode state for one block."""
    if cfg.kind in ("dense", "moe"):
        return {"attn": init_cache(cfg.attn, batch, max_len, dtype)}
    if cfg.kind == "hymba":
        return {
            "attn": init_cache(cfg.attn, batch, max_len, dtype),
            "ssm": init_ssm_state(cfg.ssm, d_model, batch),
        }
    if cfg.kind == "mlstm":
        return {"cell": init_mlstm_state(cfg.attn.n_heads, d_model, batch)}
    if cfg.kind == "slstm":
        return {"cell": init_slstm_state(d_model, batch)}
    raise ValueError(cfg.kind)


def block_apply(p, x, cfg: BlockConfig, cache=None, positions=None, pos3d=None,
                odin: Optional[OdinConfig] = None, norm_eps: float = 1e-5,
                moe_no_drop: bool = False, tables=None,
                spec_decode: bool = False, mixed=None,
                moe_held: bool = False):
    """(params, x [B,S,d], cache) → (x', cache').  ``tables``: per-slot block
    tables when the attention cache is the paged block pool (serving);
    ``spec_decode``: the S tokens are a speculative draft tile (paged
    attention takes the multi-token-query kernel path); ``mixed``: the row
    layout (``nn.attention.MixedRows``) of a mixed prefill+decode dispatch
    whose rows are flat in ``x [1, N, d]`` (paged GQA only).  ``moe_held``
    (MoE blocks): return ``(x', cache', held [B, S])``, each row's
    assignments to the experts this layer holds."""
    new_cache = dict(cache) if cache is not None else None
    if mixed is not None and cfg.kind not in ("dense", "moe"):
        raise ValueError("mixed dispatch supports paged GQA blocks only")
    if cfg.kind in ("dense", "moe"):
        a, ac = attention(p["attn"], rmsnorm(x, p["ln1"], norm_eps), cfg.attn,
                          positions=positions, pos3d=pos3d,
                          cache=None if cache is None else cache["attn"], odin=odin,
                          tables=tables, spec_decode=spec_decode, mixed=mixed,
                          norm_eps=norm_eps)
        x = x + a
        h = rmsnorm(x, p["ln2"], norm_eps)
        if new_cache is not None:
            new_cache["attn"] = ac
        if cfg.kind == "dense":
            return x + _mlp(p["mlp"], h, cfg.activation, odin), new_cache
        y = moe_block(p["moe"], h, cfg.moe, cfg.activation,
                      no_drop=moe_no_drop, odin=odin, with_held=moe_held)
        if moe_held:
            y, held = y
            return x + y, new_cache, held
        return x + y, new_cache

    if cfg.kind == "hymba":
        h = rmsnorm(x, p["ln1"], norm_eps)
        a, ac = attention(p["attn"], h, cfg.attn, positions=positions, pos3d=pos3d,
                          cache=None if cache is None else cache["attn"], odin=odin,
                          tables=tables, norm_eps=norm_eps)
        s, sc = ssm_block(p["ssm"], h, cfg.ssm,
                          state=None if cache is None else cache["ssm"], odin=odin)
        # Hymba fusion: per-branch output norm, learnable per-channel mix
        fused = 0.5 * (
            p["mix_beta"][0] * rmsnorm(a, p["attn_out_norm"], norm_eps).astype(jnp.float32)
            + p["mix_beta"][1] * rmsnorm(s, p["ssm_out_norm"], norm_eps).astype(jnp.float32)
        )
        x = x + fused.astype(x.dtype)
        x = x + _mlp(p["mlp"], rmsnorm(x, p["ln2"], norm_eps), cfg.activation, odin)
        if new_cache is not None:
            new_cache["attn"], new_cache["ssm"] = ac, sc
        return x, new_cache

    if cfg.kind == "mlstm":
        y, st = mlstm_block(p["cell"], rmsnorm(x, p["ln1"], norm_eps), cfg.attn.n_heads,
                            state=None if cache is None else cache["cell"], odin=odin,
                            impl=cfg.mlstm_impl)
        x = x + y
        if new_cache is not None:
            new_cache["cell"] = st
        return x, new_cache

    if cfg.kind == "slstm":
        y, st = slstm_block(p["cell"], rmsnorm(x, p["ln1"], norm_eps),
                            state=None if cache is None else cache["cell"], odin=odin)
        x = x + y
        if new_cache is not None:
            new_cache["cell"] = st
        return x, new_cache
    raise ValueError(cfg.kind)
