"""Mixture-of-Experts: top-k router + capacity-based scatter dispatch.

Dispatch is the sort-based MegaBlocks/MaxText-style static-shape algorithm:
flatten (token, k) assignments, rank each within its expert via a stable sort,
drop overflow beyond ``capacity``, scatter into a dense ``[E, C, d]`` buffer,
run per-expert matmuls (one grouped einsum — experts axis shards on "model"
for expert parallelism), gather back, and gate-weight the combine.  No
``[T, E, C]`` one-hot tensors are ever materialized (they would be ~TB-scale
at the assigned shapes).

Router: softmax over fp32 logits, top-k.  DeepSeek-style extensions: shared
(always-on) experts and the aux-loss-free bias (a non-learned, per-expert
bias added to routing scores only for *selection*, not for the gate weight).

Expert share (expert parallelism): a layer may hold only a contiguous range
of the router's experts (``MoEConfig.n_held`` from ``held_start``).  It
still routes over all of them and normalises the top-k gates over all k
picks, but dispatches only the assignments its own experts take — the
others are dropped before dispatch and take no capacity — and returns the
held experts' part of the output.  The shares of all chips add up to the
whole layer; the exchange that would add them is not part of this layer.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core.odin_linear import OdinConfig
from repro.nn.layers import activation, linear, linear_spec
from repro.nn.module import ParamSpec
from repro.nn.pcontext import constrain

__all__ = ["moe_spec", "moe_block", "dispatch_indices"]


def moe_spec(cfg: MoEConfig, d_model: int) -> Dict[str, ParamSpec]:
    E, Eh, F = cfg.n_experts, cfg.held, cfg.d_ff
    if not (0 <= cfg.held_start and cfg.held_start + Eh <= E and cfg.top_k <= E):
        raise ValueError(f"experts [{cfg.held_start}, {cfg.held_start + Eh}) "
                         f"and top-{cfg.top_k} do not fit a router of {E}")
    spec = {
        "router": ParamSpec((d_model, E), ("embed", None), jnp.float32, init="fan_in"),
        "w_gate": ParamSpec((Eh, d_model, F), ("experts", "embed", "mlp"), init="fan_in"),
        "w_up": ParamSpec((Eh, d_model, F), ("experts", "embed", "mlp"), init="fan_in"),
        "w_down": ParamSpec((Eh, F, d_model), ("experts", "mlp", "embed"), init="fan_in"),
    }
    if cfg.aux_free_bias:
        spec["route_bias"] = ParamSpec((E,), (None,), jnp.float32, init="zeros")
    if cfg.n_shared:
        S = cfg.n_shared * cfg.d_ff
        spec["shared_gate"] = linear_spec(d_model, S, ("embed", "mlp"))
        spec["shared_up"] = linear_spec(d_model, S, ("embed", "mlp"))
        spec["shared_down"] = linear_spec(S, d_model, ("mlp", "embed"))
    return spec


def dispatch_indices(expert_ids: jax.Array, n_experts: int, capacity: int):
    """Rank each (token·k) assignment within its expert; static shapes.

    expert_ids: int32 [A].  Returns (slot [A], keep [A]) where
    ``slot = expert·C + rank`` for kept assignments (rank < capacity) and
    the out-of-bounds sentinel ``E·C`` for dropped ones — scatters must use
    ``mode="drop"`` (a 0 sentinel would clobber expert 0's first slot).
    An id of ``n_experts`` (an expert not held here) sorts after every held
    one, so it takes no rank of theirs, and is dropped.
    """
    A = expert_ids.shape[0]
    order = jnp.argsort(expert_ids, stable=True)               # assignments grouped by expert
    sorted_ids = expert_ids[order]
    # rank within group = index - start index of that expert's run
    starts = jnp.searchsorted(sorted_ids, jnp.arange(n_experts), side="left")
    rank_sorted = jnp.arange(A, dtype=jnp.int32) - starts[sorted_ids]
    rank = jnp.zeros((A,), jnp.int32).at[order].set(rank_sorted)
    keep = (rank < capacity) & (expert_ids < n_experts)
    slot = jnp.where(keep, expert_ids * capacity + rank, n_experts * capacity)
    return slot, keep


def moe_block(p, x: jax.Array, cfg: MoEConfig, activation_kind: str = "swiglu",
              no_drop: bool = False, odin: Optional[OdinConfig] = None,
              with_held: bool = False):
    """x: [B, S, d] → [B, S, d]: the held experts' part of the layer output
    (the whole output when every expert is held).  ``with_held`` also
    returns ``[B, S]`` int32: how many of each row's top-k assignments
    landed on a held expert."""
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    E, Eh, k = cfg.n_experts, cfg.held, cfg.top_k

    with jax.named_scope("moe/route"):
        logits = (xt.astype(jnp.float32) @ p["router"])        # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        select_scores = probs + (p["route_bias"][None, :] if "route_bias" in p else 0.0)
        _, top_idx = jax.lax.top_k(select_scores, k)           # [T, k]
        gates = jnp.take_along_axis(probs, top_idx, axis=-1)   # gate from *unbiased* probs
        # normalised over all k picks, held here or not
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        A = T * k
        expert_ids = top_idx.reshape(A).astype(jnp.int32)
        if Eh != E:
            # local ids of the held range; the rest become Eh and are dropped
            expert_ids = expert_ids - cfg.held_start
            expert_ids = jnp.where((expert_ids >= 0) & (expert_ids < Eh),
                                   expert_ids, Eh)
        held = None
        if with_held:
            held = (expert_ids < Eh).reshape(T, k).sum(-1, dtype=jnp.int32)

    if no_drop or S == 1:
        # Inference-exact routing: capacity overflow resolves in batch order,
        # so a dropped assignment couples one token's output to what else
        # shares the batch — and makes prefill outputs depend on the total
        # token count.  Both break serving invariants: decode slots must be
        # isolated from co-batched (even garbage) slots, and chunked or
        # recompute-replay prefill must route each token exactly like the
        # original pass did.  Decode (S == 1) is therefore ALWAYS drop-free —
        # including the dry-run decode cells, whose cost artifacts now
        # reflect what a serving-correct decode actually pays ([E, B, d]
        # dispatch buffer instead of the capped [E, B·k·cf/E, d]).  Prefill
        # and training keep the capped capacity unless ``no_drop`` is set;
        # the serving prefill path sets it and bounds T by the chunk length.
        capacity = T
    else:
        capacity = max(1, int(T * k * cfg.capacity_factor / E))

    with jax.named_scope("moe/experts"):
        slot, keep = dispatch_indices(expert_ids, Eh, capacity)
        token_idx = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        # Dispatch in GATHER form: scatter only scalar assignment ids into
        # the slot table, then gather token rows.  A row-wise
        # ``buf.at[slot].set(x)`` scatter lowers to index matrices
        # materialized at [E·C, d] (≈240 GB u32 at the 671B train cell); the
        # scalar scatter is [E·C] ints.
        slot_to_assign = jnp.full((Eh * capacity,), A, jnp.int32)
        slot_to_assign = slot_to_assign.at[slot].set(
            jnp.where(keep, jnp.arange(A, dtype=jnp.int32), A), mode="drop")
        token_for_slot = jnp.concatenate([token_idx, jnp.zeros((1,), jnp.int32)])[slot_to_assign]
        filled = (slot_to_assign < A)[:, None]
        buf = jnp.where(filled, xt[token_for_slot], 0)
        buf = buf.reshape(Eh, capacity, d)
        # EP sharding hint: experts on "model", capacity on data — keeps the
        # [E, C, d] buffer (≈150 GB at the 671B train cell) distributed
        # instead of replicated (no-op outside a logical_sharding context).
        buf = constrain(buf, ("experts", "capacity", None))

        # per-expert FFN — grouped einsums; 'experts' axis shards (EP)
        g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype))
        if activation_kind == "swiglu":
            h = jax.nn.silu(g) * u
        elif activation_kind == "relu2":
            r = jax.nn.relu(g)
            h = r * r * u
        else:
            h = jax.nn.gelu(g) * u
        y = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))
        y = y.reshape(Eh * capacity, d)

        # combine: gather each assignment's expert output, weight by gate,
        # sum over k
        out_per_assign = jnp.where(keep[:, None], y[slot], 0)  # [A, d]
        out_per_assign = constrain(out_per_assign, ("capacity", None))
        weighted = out_per_assign * gates.reshape(A, 1).astype(x.dtype)
        out = jax.ops.segment_sum(weighted, token_idx, num_segments=T)
        out = constrain(out, ("capacity", None))

    if "shared_gate" in p:
        sg = jax.nn.silu(linear(xt, p["shared_gate"], odin)) * linear(xt, p["shared_up"], odin)
        out = out + linear(sg, p["shared_down"], odin)
    out = out.reshape(B, S, d)
    if with_held:
        return out, held.reshape(B, S)
    return out
