"""Operations and bytes the work needs, computed from shapes.

Counts are of the useful work, whatever implements it: a token's layer
work at its own context, the LM head only where its logits are used, and
for the paged-attention kernel only the valid keys and values (rows below
the slot's length).  Padding, dead pages and recomputation are not counted,
so a share of a peak computed from these numbers cannot pass 100% unless
the time leaves out part of the work.

``hf`` is a configuration file's ``hf_config``: the published keys, as run.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2


def _dims(hf: Dict):
    d = hf["hidden_size"]
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or d // H
    return d, H, Hkv, D, hf["intermediate_size"], hf["num_hidden_layers"]


def layer_matmul_flops(hf: Dict) -> float:
    """Projection and MLP operations of one token through every layer."""
    d, H, Hkv, D, ff, L = _dims(hf)
    per_layer = 2 * (d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * ff)
    return float(L * per_layer)


def attention_flops(hf: Dict, kv_len: int) -> float:
    """Scores and weighted values of one query row over ``kv_len`` keys,
    every layer (``2·H·D`` for q·k and as much for p·v, per key)."""
    d, H, Hkv, D, ff, L = _dims(hf)
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
    d, H, Hkv, D, ff, L = _dims(hf)
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
    d, H, Hkv, D, ff, L = _dims(hf)
    return float(L * (2 * kv_len * Hkv * D * kv_bytes + 2 * H * D * BF16))
