"""The serving engine against a plain reference, Qwen3-MoE at smoke size.

The engine serves the registry's Qwen3-MoE smoke model (per-head q/k
RMSNorm, 8 routed experts, top-2) holding 2 of its 8 experts, through its
normal path: the paged block pool, chunked prefill staged through mixed
dispatches beside running decode rows, then decode.  The logits behind
every token it serves are compared with the reference's full forward pass
over the same sequence: a float64 NumPy decoder written from the published
description, which imports nothing of the program and reads the weights by
their names in the weight tree.  The reference keeps the same expert share
(the held experts' part of each MoE layer), as every chip of the deployment
computes.

The program runs in float32 here (weights, activations and the KV pool),
so the comparison is tight: what is left is float32 rounding.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import steps
from repro.models import lm, registry
from repro.nn import module as nnmod
from repro.serving import Request, ServingEngine

LO, HELD = 2, 2            # the share: experts 2 and 3 of the router's 8
EPS, THETA = 1e-6, 1e6     # Qwen3's published rms_norm_eps and rope_theta

# Largest |program − reference| logit allowed.  The logits here are O(1)
# (the largest about 4.5); float32 rounding through two layers, the pool
# and the paged kernel's online softmax leaves about 1e-6 of them.  1e-4
# leaves 100x room for rounding, and lies four orders under what leaving out
# the q/k norm, or normalising the gates over the held picks only, moves a
# logit (about 1.5 each: the last test below).
TOL = 1e-4


def _config():
    cfg = registry.get_smoke("qwen3-moe-235b-a22b")
    b = cfg.blocks[0]
    b = dataclasses.replace(
        b, attn=dataclasses.replace(b.attn, rope_theta=THETA),
        moe=dataclasses.replace(b.moe, n_held=HELD, held_start=LO))
    return cfg.with_overrides(blocks=(b,), norm_eps=EPS, kv_dtype="float32")


def _weights(cfg, seed=0):
    """Seeded float32 weights: the program's tree, every leaf redrawn so
    the logits are O(1) and no norm gain is 1 (a gain left out shows)."""
    spec = nnmod.abstract(lm.param_spec(cfg))
    leaves, tree = jax.tree_util.tree_flatten_with_path(spec)
    rng = np.random.default_rng(seed)
    out = []
    for path, sds in leaves:
        name = str(path[-1].key)
        if "norm" in name or name.startswith("ln"):
            a = 1 + 0.3 * rng.standard_normal(sds.shape)
        elif name == "embed":
            a = rng.standard_normal(sds.shape)
        else:
            a = rng.standard_normal(sds.shape) / np.sqrt(sds.shape[-2])
        out.append(jnp.asarray(a, jnp.float32))
    return jax.tree_util.tree_unflatten(tree, out)


# --------------------------------------------------------------- reference

def _rms(x, g):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + EPS) * g


def _rope(x):
    """Half-split rotary embedding of x [T, H, D] at positions 0..T-1."""
    D = x.shape[-1]
    ang = np.arange(len(x))[:, None, None] * THETA ** (-np.arange(0, D, 2) / D)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x1 * np.sin(ang) + x2 * np.cos(ang)], -1)


def reference_logits(w, tokens, hp, held=(LO, LO + HELD), renorm_all=True,
                     qk_norm=True):
    """Logits [T, V] of the full forward pass over ``tokens`` [T]: embed;
    per layer RMSNorm → q/k/v → per-head q/k RMSNorm → RoPE → causal GQA →
    o → residual → RMSNorm → softmax router over every expert → top-k →
    gates over the k picks → the held experts' SwiGLU, weighted → residual;
    final RMSNorm; untied head."""
    H, Hkv, D, E, k = hp
    f = lambda a: np.asarray(a, np.float64)
    seg = jax.tree.map(f, w["segments"][0])
    x = f(w["embed"])[tokens]
    T = len(tokens)
    for l in range(seg["ln1"].shape[0]):
        a, m = (jax.tree.map(lambda v: v[l], seg[n]) for n in ("attn", "moe"))
        h = _rms(x, seg["ln1"][l])
        q = (h @ a["q"]).reshape(T, H, D)
        kk = (h @ a["k"]).reshape(T, Hkv, D)
        v = (h @ a["v"]).reshape(T, Hkv, D)
        if qk_norm:
            q, kk = _rms(q, a["q_norm"]), _rms(kk, a["k_norm"])
        q, kk = _rope(q), np.repeat(_rope(kk), H // Hkv, axis=1)
        v = np.repeat(v, H // Hkv, axis=1)
        s = np.einsum("qhd,khd->hqk", q, kk) / np.sqrt(D)
        s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        o = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)
        x = x + o.reshape(T, H * D) @ a["o"]
        h = _rms(x, seg["ln2"][l])
        z = h @ m["router"]
        probs = np.exp(z - z.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        y = np.zeros_like(x)
        for t in range(T):
            top = np.argsort(-probs[t], kind="stable")[:k]
            mine = [e for e in top if held[0] <= e < held[1]]
            norm = probs[t, top if renorm_all else mine].sum()
            for e in mine:
                j = e - held[0]
                g = h[t] @ m["w_gate"][j]
                act = g / (1 + np.exp(-g)) * (h[t] @ m["w_up"][j])
                y[t] += probs[t, e] / norm * (act @ m["w_down"][j])
        x = x + y
    return _rms(x, f(w["final_norm"])) @ f(w["lm_head"])


# ------------------------------------------------------------------ serve

@pytest.fixture(scope="module")
def served():
    """Serve four requests on three slots with 8-row prefill chunks and the
    logits behind every served token, read where the program samples them.
    Returns (cfg, weights, [(prompt, tokens, logits [n, V])], stats)."""
    cfg = _config()
    w = _weights(cfg)
    seen = []                          # per dispatch: the per-slot logits
    sample = steps._sample_tokens

    def spy(logits, cfg_, key, temperature, top_k):
        jax.debug.callback(lambda a: seen.append(np.asarray(a)),
                           logits[:, -1])
        return sample(logits, cfg_, key, temperature, top_k)

    rows = {}
    emit = ServingEngine._emit

    def record(self, req, tok, now):
        logit = seen[-1][req.slot]
        rows.setdefault(req.rid, []).append(logit)
        return emit(self, req, tok, now)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(steps, "_sample_tokens", spy)
        mp.setattr(ServingEngine, "_emit", record)
        eng = ServingEngine(cfg, slots=3, max_len=64, block_size=8,
                            prefill_chunk=8, params=w)
        assert eng.paged and eng.mixed
        rng = np.random.default_rng(1)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n)
                        .astype(np.int32), max_new=m)
                for i, (n, m) in enumerate([(19, 7), (5, 9), (27, 6),
                                            (12, 8)])]
        eng.run(reqs)
    # prompts staged through mixed dispatches, and decode-only dispatches
    # (a mixed dispatch counts as at most one decode dispatch)
    assert eng.stats.mixed_dispatches
    assert eng.stats.decode_dispatches > eng.stats.mixed_dispatches
    out = []
    for r in reqs:
        toks = [int(np.ravel(t)[0]) for t in r.generated]
        assert len(toks) == r.max_new
        logits = np.stack(rows[r.rid])
        # the spy read the logits the program sampled (greedy)
        assert (logits.argmax(-1) == toks).all()
        out.append((r.prompt, np.asarray(toks), logits))
    return cfg, w, out, eng.stats


def _hp(cfg):
    b = cfg.blocks[0]
    return (b.attn.n_heads, b.attn.n_kv_heads, b.attn.d_head,
            b.moe.n_experts, b.moe.top_k)


def _gaps(served_, **ref_kw):
    cfg, w, out, _ = served_
    worst = 0.0
    for prompt, toks, logits in out:
        seq = np.concatenate([prompt, toks[:-1]])
        ref = reference_logits(w, seq, _hp(cfg), **ref_kw)[len(prompt) - 1:]
        worst = max(worst, float(np.abs(ref - logits).max()))
    return worst


def test_served_logits_match_the_reference(served):
    """Every served position, prefill's first token and each decoded one,
    matches the reference's full forward pass."""
    assert _gaps(served) < TOL


def test_counters_count_the_share(served):
    """Each real row routes top_k per MoE layer; the held count is the
    device's; every held expert computes one row per dispatched row."""
    cfg, w, out, st = served
    b = cfg.blocks[0]
    assert st.moe_routed == b.moe.top_k * b.n_layers * (
        st.prefill_tokens + st.decode_tokens)
    assert 0 < st.moe_held < st.moe_routed
    assert st.moe_rows % (HELD * b.n_layers) == 0
    assert st.moe_rows >= HELD * b.n_layers * (st.prefill_tokens
                                               + st.decode_tokens)


@pytest.mark.parametrize("change", ["no_qk_norm", "gates_over_held"])
def test_the_tolerance_tells_a_missing_piece(served, change):
    """A reference that leaves out the q/k norm, or normalises the gates
    over the held picks only, lies far outside the tolerance: the test
    above would fail a program that did either."""
    kw = ({"qk_norm": False} if change == "no_qk_norm"
          else {"renorm_all": False})
    assert _gaps(served, **kw) > 100 * TOL


def test_registry_takes_the_published_values():
    """Qwen/Qwen3-235B-A22B's config.json: rope_theta 1e6, rms_norm_eps
    1e-6, q/k norm, 128 experts routed and held, top-8 of width 1536."""
    cfg = registry.get_config("qwen3-moe-235b-a22b")
    b = cfg.blocks[0]
    assert (cfg.norm_eps, cfg.vocab, cfg.tie_embeddings, b.n_layers) == (
        1e-6, 151_936, False, 94)
    assert (b.attn.rope_theta, b.attn.qk_norm, b.attn.n_heads,
            b.attn.n_kv_heads, b.attn.d_head) == (1e6, True, 64, 4, 128)
    assert (b.moe.n_experts, b.moe.held, b.moe.top_k, b.moe.d_ff,
            b.moe.n_shared) == (128, 128, 8, 1536, 0)
