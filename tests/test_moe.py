"""MoE router/dispatch properties and block behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional test dep; collection must not hard-fail
from hypothesis import given, settings, strategies as st

from repro.configs.base import MoEConfig
from repro.nn.moe import dispatch_indices, moe_block, moe_spec
from repro.nn.module import materialize


@given(st.integers(0, 2**31 - 1), st.integers(2, 16), st.integers(1, 8),
       st.integers(4, 64))
@settings(max_examples=40, deadline=None)
def test_dispatch_invariants(seed, n_experts, capacity, A):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, n_experts, A), jnp.int32)
    slot, keep = dispatch_indices(ids, n_experts, capacity)
    slot, keep, ids = np.asarray(slot), np.asarray(keep), np.asarray(ids)

    # kept slots are unique and land in the owning expert's range
    ks = slot[keep]
    assert len(set(ks.tolist())) == len(ks)
    assert ((ks // capacity) == ids[keep]).all()
    # dropped assignments route to the OOB sentinel (never slot 0)
    assert (slot[~keep] == n_experts * capacity).all()
    # per-expert kept count = min(arrivals, capacity)
    for e in range(n_experts):
        arrived = int((ids == e).sum())
        kept = int(((ids == e) & keep).sum())
        assert kept == min(arrived, capacity)


def test_moe_single_expert_equals_dense():
    """E=1, top-1, ample capacity ⇒ MoE == its own expert FFN exactly."""
    cfg = MoEConfig(n_experts=1, top_k=1, d_ff=32, capacity_factor=1.0,
                    aux_free_bias=False)
    d = 16
    p = materialize(moe_spec(cfg, d), jax.random.PRNGKey(0))
    x = (jax.random.normal(jax.random.PRNGKey(1), (2, 10, d)) * 0.5)
    y = moe_block(p, x, cfg)

    xt = x.reshape(-1, d)
    g = jnp.einsum("td,df->tf", xt, p["w_gate"][0])
    u = jnp.einsum("td,df->tf", xt, p["w_up"][0])
    ref = jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, p["w_down"][0]).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(ref, np.float32),
                               atol=1e-5)


def test_moe_capacity_drop_zeroes_tokens():
    """With capacity 0-ish, overflow tokens contribute nothing (not garbage)."""
    cfg = MoEConfig(n_experts=2, top_k=1, d_ff=8, capacity_factor=0.26,
                    aux_free_bias=False)
    d = 4
    p = materialize(moe_spec(cfg, d), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, d))
    y = moe_block(p, x, cfg)                      # capacity = 1 per expert
    assert bool(jnp.isfinite(y).all())
    # at most 2 tokens (1/expert) can be nonzero
    nonzero = int((jnp.abs(y[0]).sum(-1) > 1e-7).sum())
    assert nonzero <= 2


def test_shared_expert_always_on():
    cfg = MoEConfig(n_experts=2, top_k=1, d_ff=8, n_shared=1, capacity_factor=0.01)
    d = 4
    p = materialize(moe_spec(cfg, d), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 6, d))
    y = moe_block(p, x, cfg)                      # capacity≈0: routed path ~dead
    assert float(jnp.abs(y).sum()) > 0            # shared expert still fires


def test_route_bias_changes_selection_not_gate():
    cfg = MoEConfig(n_experts=4, top_k=1, d_ff=8, aux_free_bias=True,
                    capacity_factor=2.0)
    d = 8
    p = materialize(moe_spec(cfg, d), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, d))
    y0 = moe_block(p, x, cfg)
    # huge bias toward expert 3 → selection flips, output changes
    p2 = dict(p)
    p2["route_bias"] = jnp.array([-10.0, -10.0, -10.0, 10.0], jnp.float32)
    y1 = moe_block(p2, x, cfg)
    assert float(jnp.abs(y1 - y0).max()) > 1e-6


def test_grad_flows_through_dispatch():
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff=16, capacity_factor=2.0)
    d = 8
    p = materialize(moe_spec(cfg, d), jax.random.PRNGKey(0))

    def loss(p, x):
        return (moe_block(p, x, cfg) ** 2).sum()

    x = jax.random.normal(jax.random.PRNGKey(5), (2, 6, d))
    g = jax.grad(loss)(p, x)
    gn = sum(float(jnp.abs(l).sum()) for l in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


# ---------------------------------------------------------------------------
# expert share: a layer that holds a contiguous range of the router's experts
# ---------------------------------------------------------------------------

def _share(p, cfg, lo, n):
    """The parameters and config of the share holding experts [lo, lo+n)."""
    import dataclasses
    ps = dict(p, **{w: p[w][lo:lo + n] for w in ("w_gate", "w_up", "w_down")})
    return ps, dataclasses.replace(cfg, n_held=n, held_start=lo)


@pytest.mark.parametrize("no_drop", [True, False])
def test_expert_shares_add_up_to_the_whole_layer(no_drop):
    """8 experts in shares of 2: the held parts of the 4 shares add up to the
    uncut layer (router width = held = 8).  That holds only if every share
    normalises its gates over the full top-k; and with capped capacity
    (``no_drop=False``, overflow on several experts) only if the assignments
    a share does not hold take none of its capacity slots."""
    cfg = MoEConfig(n_experts=8, top_k=2, d_ff=16, capacity_factor=1.0,
                    aux_free_bias=False)
    d = 16
    p = materialize(moe_spec(cfg, d), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, d))
    whole = moe_block(p, x, cfg, no_drop=no_drop)
    parts, held = 0.0, 0
    for lo in range(0, 8, 2):
        ps, share = _share(p, cfg, lo, 2)
        y, h = moe_block(ps, x, share, no_drop=no_drop, with_held=True)
        parts, held = parts + y, held + h
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    # every assignment lands on exactly one share
    assert (np.asarray(held) == cfg.top_k).all()
    if not no_drop:                # capacity 6 a expert did drop some
        free = moe_block(p, x, cfg, no_drop=True)
        assert not np.allclose(np.asarray(whole), np.asarray(free))


def test_share_gates_normalise_over_the_full_top_k():
    """One share's output is Σ over its held picks of g·FFN(x), each g the
    pick's probability over the sum of all k picks' probabilities."""
    cfg = MoEConfig(n_experts=8, top_k=3, d_ff=8, aux_free_bias=False)
    d = 8
    p = materialize(moe_spec(cfg, d), jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 10, d))
    ps, share = _share(p, cfg, 4, 3)
    y = np.asarray(moe_block(ps, x, share, no_drop=True))[0]
    xt = np.asarray(x[0], np.float64)
    probs = np.asarray(jax.nn.softmax(x[0] @ p["router"]), np.float64)
    w = {n: np.asarray(p[n], np.float64) for n in ("w_gate", "w_up", "w_down")}
    ref = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        top = np.argsort(-probs[t])[:3]
        for e in top:
            if 4 <= e < 7:
                g = probs[t, e] / probs[t, top].sum()
                a = xt[t] @ w["w_gate"][e]
                h = a / (1 + np.exp(-a)) * (xt[t] @ w["w_up"][e])
                ref[t] += g * (h @ w["w_down"][e])
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("seed", range(5))
def test_dispatch_drops_experts_not_held(seed):
    """Ids of experts not held (``n_experts``) are dropped and take no rank:
    the held assignments get the slots they get without them."""
    rng = np.random.default_rng(seed)
    E, C, A = 3, 2, 24
    ids = rng.integers(0, E + 1, A)                  # E: held elsewhere
    slot, keep = map(np.asarray, dispatch_indices(jnp.asarray(ids, jnp.int32), E, C))
    mine = ids < E
    s2, k2 = map(np.asarray, dispatch_indices(jnp.asarray(ids[mine], jnp.int32), E, C))
    assert (slot[mine] == s2).all() and (keep[mine] == k2).all()
    assert not keep[~mine].any() and (slot[~mine] == E * C).all()


def test_all_experts_held_is_the_uncut_layer():
    """Held = router width: ``n_held`` set or left 0 gives the same layer,
    bit for bit, and every assignment is counted as held."""
    import dataclasses
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff=8, capacity_factor=1.0,
                    aux_free_bias=True)
    d = 8
    p = materialize(moe_spec(cfg, d), jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 6, d))
    y0 = moe_block(p, x, cfg)
    y1, held = moe_block(p, x, dataclasses.replace(cfg, n_held=4),
                         with_held=True)
    assert np.array_equal(np.asarray(y0), np.asarray(y1))
    assert (np.asarray(held) == 2).all()
    with pytest.raises(ValueError, match="do not fit"):
        moe_spec(dataclasses.replace(cfg, n_held=2, held_start=3), d)


# ---------------------------------------------------------------------------
# per-head q/k RMSNorm (Qwen3)
# ---------------------------------------------------------------------------

def _attn(qk_norm):
    from repro.configs.base import AttnConfig
    return AttnConfig(kind="gqa", n_heads=4, n_kv_heads=2, d_head=8,
                      rope_theta=1e6, qk_norm=qk_norm)


def _count(jaxpr, name):
    n = 0
    for eq in jaxpr.eqns:
        n += eq.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eq.params):
            n += _count(sub, name)
    return n


def test_qk_norm_is_the_formula():
    """q and k normed per head over d_head with their gains and the model's
    eps, after the projections and before RoPE; then causal GQA."""
    from repro.nn.attention import attention, attn_spec
    cfg, d, T, eps = _attn(True), 16, 7, 1e-6
    p = materialize(attn_spec(cfg, d), jax.random.PRNGKey(3))
    p = dict(p, q_norm=1 + 0.3 * jax.random.normal(jax.random.PRNGKey(4), (8,)),
             k_norm=1 + 0.3 * jax.random.normal(jax.random.PRNGKey(5), (8,)))
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, T, d))
    y, _ = attention(p, x, cfg, norm_eps=eps)
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    xt = np.asarray(x[0], np.float64)

    def heads(a, g, n):
        a = a.reshape(T, n, 8)
        a = a / np.sqrt(np.mean(a * a, -1, keepdims=True) + eps) * g
        ang = np.arange(T)[:, None, None] * 1e6 ** (-np.arange(0, 8, 2) / 8)
        a1, a2 = a[..., :4], a[..., 4:]
        return np.concatenate([a1 * np.cos(ang) - a2 * np.sin(ang),
                               a1 * np.sin(ang) + a2 * np.cos(ang)], -1)

    q = heads(xt @ w["q"], w["q_norm"], 4)
    k = np.repeat(heads(xt @ w["k"], w["k_norm"], 2), 2, axis=1)
    v = np.repeat((xt @ w["v"]).reshape(T, 2, 8), 2, axis=1)
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(8)
    s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
    a = np.exp(s - s.max(-1, keepdims=True))
    o = np.einsum("hqk,khd->qhd", a / a.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(np.asarray(y[0]), o.reshape(T, 32) @ w["o"],
                               rtol=1e-4, atol=1e-5)


def test_qk_norm_off_adds_nothing():
    """Flag off, as every model but Qwen3's: no norm parameter and no norm
    op — the attention traces with no rsqrt at all; flag on adds exactly
    the two norms."""
    from repro.nn.attention import attention, attn_spec
    x = jnp.zeros((1, 5, 16))
    n = {}
    for flag in (False, True):
        spec = attn_spec(_attn(flag), 16)
        assert ("q_norm" in spec) == ("k_norm" in spec) == flag
        p = materialize(spec, jax.random.PRNGKey(0))
        jaxpr = jax.make_jaxpr(lambda p, x: attention(p, x, _attn(flag))[0])(p, x)
        n[flag] = _count(jaxpr.jaxpr, "rsqrt")
    assert n == {False: 0, True: 2}
