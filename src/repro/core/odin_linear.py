"""ODIN execution modes for a linear layer — the paper's technique as a drop-in.

Three modes, sharing one quantization boundary (DESIGN.md §2):

``exact``  — plain matmul (fp32/bf16), the reference numerics.
``int8``   — deterministic *expected value* of the stochastic pipeline: int8
             operands, integer dot (TPU MXU ``int8×int8→int32``), identical
             1/K̂ MUX-tree scaling and optional 8-bit popcount rounding.  This
             is the deployment surrogate for large models.
``sc``     — bit-faithful stochastic arithmetic: B→S LUTs, bit-parallel AND,
             MUX tree, popcount (paper §IV).  Runs the fused Pallas kernel on
             TPU (kernels/sc_mac) or the jnp reference; intended for
             paper-scale layers, not 100B-parameter matmuls.

Signed operands use two-rail decomposition with binary-domain recombination
(core/quant.py docstring), mirroring ODIN's hybrid binary/stochastic split.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp

from repro.core import stochastic as sc
from repro.core.quant import quantize_signed_tworail, quantize_unipolar

__all__ = ["OdinConfig", "odin_linear", "get_luts"]


@dataclass(frozen=True)
class OdinConfig:
    mode: str = "exact"                   # exact | int8 | sc
    stream_len: int = 256
    n_levels: int = 256
    signed_activations: bool = True       # False after ReLU (paper's CNN case)
    round_popcount: bool = False          # model 8-bit S_TO_B output rounding
    use_pallas: bool = False              # sc mode: fused kernel vs jnp reference
    interpret: bool | None = None         # Pallas interpret mode; None ⇒ off the TPU
    lut_seed: int = 0
    # SC accumulation granularity.  0 ⇒ one full MUX tree over K (the naive
    # reading of the paper — at K ≳ stream_len the 1/K̂ subsampling leaves
    # <1 stream bit per product and deep-layer signal collapses; measured in
    # examples/odin_inference.py).  >0 ⇒ per-block MUX subtree + popcount +
    # *binary* accumulation across blocks — consistent with ODIN's own
    # 32-operand row/command granularity (B_TO_S/S_TO_B move 32 operands;
    # one PINATUBO row activation covers 32 operand pairs), and the reading
    # that reproduces the paper's "minimal accuracy loss" claim.
    sc_block_k: int = 32
    # PCRAM resistance-drift analog (fault injection): >0 perturbs the SC/int8
    # output multiplicatively with seeded Gaussian noise of this relative σ —
    # the readout excursion a drifted cell produces, NOT a reprogrammed
    # weight.  0.0 (default) is a no-op; ``exact`` mode is never perturbed
    # (it is the reference numerics the guards compare against).
    drift_noise: float = 0.0
    drift_seed: int = 0

    @property
    def spec(self) -> sc.StreamSpec:
        return sc.StreamSpec(self.stream_len, self.n_levels)


@functools.lru_cache(maxsize=16)
def get_luts(stream_len: int, n_levels: int, lut_seed: int, max_depth: int = 20):
    """Deterministic LUT/select-stream constants (the per-bank SRAM contents)."""
    spec = sc.StreamSpec(stream_len, n_levels)
    k = jax.random.PRNGKey(lut_seed)
    ka, kw, ks = jax.random.split(k, 3)
    lut_a = sc.make_lut(ka, spec)
    lut_w = sc.make_lut(kw, spec)
    selects = sc.make_select_streams(ks, max_depth, spec)
    return lut_a, lut_w, selects


def _rail_matmul(a_q, w_q, cfg: OdinConfig, luts=None):
    """One unipolar rail-pair product, returned in integer-dot units (Σ a·w).

    ``luts`` is the shared ``(lut_a, lut_w, selects)`` bundle for sc mode —
    fetched ONCE per :func:`odin_linear` call and reused across the four
    signed-rail products instead of being re-derived per rail.
    """
    spec = cfg.spec
    K = a_q.shape[-1]
    khat = 1 << sc.tree_depth(K)
    if cfg.mode == "sc":
        lut_a, lut_w, selects = luts
        block_k = cfg.sc_block_k
        if block_k and khat > block_k:
            # hybrid: per-block MUX subtree + popcount, binary accumulate
            if cfg.use_pallas:
                from repro.kernels.sc_mac.ops import sc_matmul_pallas

                pop = sc_matmul_pallas(a_q, w_q, lut_a, lut_w, selects, spec,
                                       interpret=cfg.interpret, max_tree_k=block_k)
                # ops.py rescales hybrid pops to full-tree units (× bk/K̂)
                return pop.astype(jnp.float32) * (khat * spec.n_levels**2 / spec.stream_len)
            from repro.kernels.sc_mac.ref import sc_matmul_hybrid_ref

            pop = sc_matmul_hybrid_ref(a_q, w_q, lut_a, lut_w, selects, spec, block_k)
            return pop.astype(jnp.float32) * (block_k * spec.n_levels**2 / spec.stream_len)
        if cfg.use_pallas:
            from repro.kernels.sc_mac.ops import sc_matmul_pallas

            pop = sc_matmul_pallas(a_q, w_q, lut_a, lut_w, selects, spec, interpret=cfg.interpret)
        else:
            pop = sc.sc_matmul(a_q, w_q, lut_a, lut_w, selects, spec)
        # popcount → integer-dot units: Σ a·w ≈ pop · K̂ L² / stream_len
        return pop.astype(jnp.float32) * (khat * spec.n_levels**2 / spec.stream_len)
    # int8 expected surrogate — identical scaling; optionally round to the
    # 8-bit popcount grid to model S_TO_B precision loss faithfully.
    dot = jnp.matmul(a_q.astype(jnp.int32), w_q.astype(jnp.int32), preferred_element_type=jnp.int32)
    if cfg.round_popcount:
        pop_scale = spec.stream_len / (khat * spec.n_levels**2)
        pop = jnp.round(dot.astype(jnp.float32) * pop_scale)
        return pop * (khat * spec.n_levels**2 / spec.stream_len)
    return dot.astype(jnp.float32)


def odin_linear(x: jax.Array, w: jax.Array, cfg: OdinConfig = OdinConfig(),
                drift_step: int = 0) -> jax.Array:
    """``x @ w`` under the configured ODIN execution mode.

    x: [..., K] activations; w: [K, N] weights.  Returns fp32 [..., N].

    ``drift_step`` keys the PCRAM drift-noise excursion in *time*: real
    resistance drift evolves between reads, so each dispatch should see a
    fresh perturbation pattern, not the same frozen one.  Callers fold their
    step counter in (a traced int32 is fine under jit); the default 0
    reproduces the old per-call-identical behavior for a fixed seed.
    """
    if cfg.mode == "exact":
        return jnp.matmul(x, w)
    if cfg.mode not in ("int8", "sc"):
        raise ValueError(f"unknown ODIN mode: {cfg.mode}")

    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)

    luts = (get_luts(cfg.stream_len, cfg.n_levels, cfg.lut_seed)
            if cfg.mode == "sc" else None)
    w_pos, w_neg, wq = quantize_signed_tworail(w)
    if cfg.signed_activations:
        a_pos, a_neg, aq = quantize_signed_tworail(x2)
        # (A⁺−A⁻)(W⁺−W⁻) — four unipolar trees, recombined in binary domain.
        out = (
            _rail_matmul(a_pos, w_pos, cfg, luts)
            + _rail_matmul(a_neg, w_neg, cfg, luts)
            - _rail_matmul(a_pos, w_neg, cfg, luts)
            - _rail_matmul(a_neg, w_pos, cfg, luts)
        )
    else:
        a_q, aq = quantize_unipolar(x2)
        out = _rail_matmul(a_q, w_pos, cfg, luts) - _rail_matmul(a_q, w_neg, cfg, luts)

    y = out * (aq.scale * wq.scale)
    if cfg.drift_noise > 0.0:
        # fold the step counter into the key so the excursion pattern moves
        # over time like real drift (PRNGKey(seed) alone froze it per call)
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.drift_seed),
                                 drift_step)
        y = y * (1.0 + cfg.drift_noise
                 * jax.random.normal(key, y.shape, jnp.float32))
    return y.reshape(*lead, w.shape[-1]).astype(jnp.float32)
