"""Tests for the fused mixed prefill+decode dispatch (token-budget packing).

Three layers:

1. ``Scheduler.pack_mixed`` properties — the packer never exceeds the row
   budget, always reserves prefill progress, and bounds decode starvation
   under pathological scarcity via its round-robin cursor.
2. End-to-end parity — mixed-on greedy token streams are bit-identical to
   mixed-off (separate prefill/decode launches) across the paged cache
   families, including under recompute preemption; non-fully-paged families
   auto-disable and forcing them raises.
3. The ride-along bugfixes — all dispatch walls live in one injectable
   clock domain (metrics ≡ stats ≡ trace under a deterministic clock), and
   the extras/chunk guard is one shared bound on both the submit and
   prefill paths.
"""
import numpy as np
import pytest

from serving_harness import materialize, mixed_spec, run_workload, token_streams

from repro.serving import Request, ServingEngine, Tracer, make_requests
from repro.serving.blocks import BlockPool
from repro.serving.scheduler import Scheduler

# the fully paged families: single-codebook GQA, MoE, multi-codebook [K, S]
MIXED_ARCHS = ["phi4-mini-3.8b", "qwen3-moe-235b-a22b", "musicgen-medium"]


# ---------------------------------------------------------------------------
# packer properties (pure scheduler, no engine)
# ---------------------------------------------------------------------------

def _sched_with(n_decoding, prefill_remaining):
    """A scheduler whose running map holds ``n_decoding`` decode-phase slots
    plus one mid-prefill slot per entry of ``prefill_remaining`` (each entry
    is the replay rows that slot still has to stage)."""
    n = n_decoding + len(prefill_remaining)
    sched = Scheduler(n, BlockPool(256, 8), max_len=512)
    slot = 0
    for _ in range(n_decoding):
        r = Request(rid=slot, prompt=np.arange(8, dtype=np.int32),
                    max_new=64, arrival=float(slot))
        r.slot = slot
        r.generated = [np.int32(1)]          # pending token → decode phase
        sched.running[slot] = r
        slot += 1
    for rem in prefill_remaining:
        r = Request(rid=slot, prompt=np.arange(rem + 4, dtype=np.int32),
                    max_new=64, arrival=float(slot))
        r.slot = slot
        r.prefilling = True
        r.prefill_pos = 4                    # rem replay rows left to stage
        sched.running[slot] = r
        slot += 1
    return sched


def test_pack_mixed_never_exceeds_budget():
    """Property: over randomized populations/budgets/chunks, one dispatch
    never packs more than ``budget`` query rows, per-slot prefill parts stay
    within ``chunk``, and assignments stay within each request's replay."""
    rng = np.random.default_rng(0)
    for _ in range(300):
        nd = int(rng.integers(0, 7))
        rems = [int(rng.integers(1, 40)) for _ in range(rng.integers(0, 4))]
        sched = _sched_with(nd, rems)
        budget = int(rng.integers(1, 24))
        chunk = int(rng.integers(1, 16))
        decode, parts = sched.pack_mixed(budget, chunk)
        rows = len(decode) + sum(c for _, _, c in parts)
        assert rows <= budget
        assert len({r.slot for r in decode}) == len(decode)
        for r, start, c in parts:
            assert 1 <= c <= chunk
            assert start == r.prefill_pos
            assert start + c <= r.cached_len


def test_pack_mixed_reserves_prefill_progress():
    """Decode rows pack first, but one row is always reserved for the oldest
    prefilling slot — TTFT can't starve behind a saturated decode population."""
    sched = _sched_with(6, [20])
    decode, parts = sched.pack_mixed(4, 8)
    assert len(decode) == 3                  # budget - reserved prefill row
    assert parts and parts[0][2] == 1        # the reserved row progresses
    # with headroom every decode slot rides and prefill takes the rest
    sched = _sched_with(3, [20])
    decode, parts = sched.pack_mixed(12, 8)
    assert len(decode) == 3
    assert sum(c for _, _, c in parts) == 8  # capped at chunk, not budget


def test_pack_mixed_decode_starvation_bounded():
    """Under pathological scarcity (budget < decode population + 1) the
    round-robin cursor bounds any slot's wait to one rotation:
    ceil(n_decoding / (budget - 1)) consecutive dispatches."""
    budget, n_dec = 4, 7
    sched = _sched_with(n_dec, [64])
    pre = sched.running[n_dec]
    cap = budget - 1                         # one row reserved for prefill
    bound = -(-n_dec // cap)                 # dispatches per full rotation
    last_ride = {s: 0 for s in range(n_dec)}
    for t in range(1, 4 * bound * n_dec):
        decode, parts = sched.pack_mixed(budget, 8)
        assert parts                         # prefill still progresses
        pre.prefill_pos = 4                  # hold it mid-prefill forever
        assert len(decode) == cap
        for r in decode:
            last_ride[r.slot] = t
        for s, last in last_ride.items():
            assert t - last < bound, f"slot {s} starved {t - last} dispatches"


def test_pack_mixed_lane_cap():
    """Property: no dispatch carries more prefill parts than the program has
    lanes, the oldest prefilling slot always holds a lane, and
    ``lane_deferred`` is set exactly when a further prefilling slot was held
    back while budget rows were left."""
    rng = np.random.default_rng(1)
    for _ in range(300):
        nd = int(rng.integers(0, 7))
        rems = [int(rng.integers(1, 40)) for _ in range(rng.integers(0, 5))]
        sched = _sched_with(nd, rems)
        budget = int(rng.integers(1, 48))
        chunk = int(rng.integers(1, 16))
        lanes = int(rng.integers(1, 4))
        decode, parts = sched.pack_mixed(budget, chunk, lanes)
        assert len(parts) <= lanes
        assert len({r.slot for r, _, _ in parts}) == len(parts)
        prefilling = sorted((r for r in sched.running.values()
                             if r.prefilling), key=lambda r: r.arrival)
        if prefilling:
            assert parts and parts[0][0] is prefilling[0]
        rows = len(decode) + sum(c for _, _, c in parts)
        held = len(prefilling) > len(parts)
        assert sched.lane_deferred == (held and len(parts) == lanes
                                       and rows < max(1, budget))


# ---------------------------------------------------------------------------
# end-to-end parity (jax)
# ---------------------------------------------------------------------------

def _staggered(**kw):
    # staggered arrivals so admitted prefills overlap in-flight decodes:
    # mixed tiles must carry both populations, not just chunked prefill
    return mixed_spec(n_requests=6, rate=40.0, gen_buckets=(6, 20), **kw)


@pytest.mark.parametrize("arch", MIXED_ARCHS)
def test_engine_mixed_token_parity(arch):
    """Mixed-on greedy streams are token-for-token equal to mixed-off while
    fused tiles actually carry both decode and prefill rows."""
    cfg, params = materialize(arch)
    base, sb = run_workload(cfg, params, max_len=64, spec=_staggered(),
                            mixed=False)
    fused, sf = run_workload(cfg, params, max_len=64, spec=_staggered(),
                             mixed=True)
    assert base == fused
    assert sb["mixed"]["dispatches"] == 0
    assert sf["mixed"]["dispatches"] > 0
    assert sf["mixed"]["prefill_rows"] > 0
    assert sf["mixed"]["decode_rows"] > 0    # decode rode along, not solo
    assert sf["prefill_tokens"] == sb["prefill_tokens"]


def test_engine_mixed_preemption_parity():
    """Recompute preemption mid-run composes with mixed dispatch: victims
    replay through fused tiles and streams still match the unconstrained
    separate-path run."""
    cfg, params = materialize("phi4-mini-3.8b")
    base, _ = run_workload(cfg, params, max_len=64, spec=_staggered(),
                           mixed=False)
    tight, st = run_workload(cfg, params, max_len=64, spec=_staggered(),
                             mixed=True, n_blocks=9)
    assert st["preemptions"]["recompute"] > 0
    assert st["mixed"]["dispatches"] > 0
    assert base == tight


def test_engine_mixed_budget_throttles_rows():
    """A tiny row budget still converges to identical streams — it just
    takes more, smaller dispatches (the budget is a shape knob, never a
    correctness knob)."""
    cfg, params = materialize("phi4-mini-3.8b")
    base, sb = run_workload(cfg, params, max_len=64, spec=_staggered(),
                            mixed=True)
    small, ss = run_workload(cfg, params, max_len=64, spec=_staggered(),
                             mixed=True, mixed_budget=4)
    assert base == small
    assert ss["mixed"]["dispatches"] > sb["mixed"]["dispatches"]
    assert ss["mixed"]["prefill_rows"] == sb["mixed"]["prefill_rows"]


def _overlapping(**kw):
    # every prompt arrives at once and spans several chunks, so prefills
    # overlap: the lane cap must hold the younger prompts back
    return mixed_spec(n_requests=5, prompt_buckets=(20, 28), gen_buckets=(4, 9),
                      **kw)


@pytest.mark.parametrize("mixed_budget", [None, 2 * 8 + 3])
def test_engine_mixed_lanes_parity(mixed_budget):
    """Overlapping prefills under one lane (the default budget) and under
    two: younger prompts wait for a lane (``mixed_prefill_deferred``), the
    program runs ``slots + lanes·q_tile`` rows per dispatch, and greedy
    streams still equal mixed-off."""
    cfg, params = materialize("phi4-mini-3.8b")
    base, _ = run_workload(cfg, params, spec=_overlapping(), mixed=False,
                           prefill_chunk=8)
    tracer = Tracer()
    eng = ServingEngine(cfg, slots=3, max_len=48, block_size=8, params=params,
                        prefill_chunk=8, mixed=True, mixed_budget=mixed_budget,
                        tracer=tracer)
    reqs = make_requests(cfg, _overlapping(), seed=9)
    eng.run(reqs)
    tracer.detach()
    assert token_streams(reqs) == base
    lanes = 1 if mixed_budget is None else 2
    assert eng.lanes == lanes
    spans = [ev for ev in tracer.events()
             if ev.ph == "X" and ev.name == "mixed"]
    st = eng.stats
    assert len(spans) == st.mixed_dispatches > 0
    assert st.mixed_prefill_deferred > 0
    assert all(ev.args["lanes"] == lanes for ev in spans)
    assert st.mixed_tile_rows == sum(3 + lanes * ev.args["q_tile"]
                                     for ev in spans)
    assert st.mixed_tile_rows == sum(ev.args["tile_rows"] for ev in spans)
    assert max(ev.args["prefill_rows"] for ev in spans) > 8 * (lanes - 1)


def _dot_rows(jaxpr):
    """Row counts of every weight matmul (a dot_general with no batch
    dimensions) in a jaxpr and its sub-jaxprs: the lhs's elements over its
    contracted size."""
    import jax
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), (lb, _) = eqn.params["dimension_numbers"]
            if not lb:
                shape = eqn.invars[0].aval.shape
                k = int(np.prod([shape[d] for d in lc]))
                out.append(int(np.prod(shape)) // k)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _dot_rows(sub)
    return out


def test_mixed_program_runs_only_carried_rows():
    """At 8 slots and a 512-row lane the mixed program's matmuls run at most
    ``slots + Q`` = 520 rows (the decode group plus one lane), never the
    padded 8 × 512 = 4,096-row tile, and the LM head runs on the 9 rows
    whose logits are read."""
    import jax
    import jax.numpy as jnp
    from repro.launch.steps import init_serving_caches, make_serving_mixed_step
    from repro.models import lm as lm_mod, registry
    from repro.nn import module as nnmod
    cfg = registry.get_smoke("phi4-mini-3.8b")
    slots, Q, bs, max_len = 8, 512, 16, 1024
    params = nnmod.abstract(lm_mod.param_spec(cfg))
    caches = jax.eval_shape(lambda: init_serving_caches(
        cfg, slots, max_len, block_size=bs, n_blocks=slots * max_len // bs))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    step = make_serving_mixed_step(cfg)
    jaxpr = jax.make_jaxpr(step)(
        params, caches, i32(slots), i32(1, Q), i32(slots),
        jax.ShapeDtypeStruct((slots,), jnp.bool_), i32(1), i32(1),
        i32(slots, max_len // bs))
    rows = _dot_rows(jaxpr.jaxpr)
    assert max(rows) == slots + Q
    assert slots + 1 in rows                 # the head: 8 decode rows + 1 lane
    assert slots * Q not in rows


def test_engine_mixed_eligibility():
    """Non-fully-paged families (hymba ring+SSM state) auto-disable mixed
    dispatch; forcing it raises instead of silently corrupting."""
    cfg, params = materialize("hymba-1.5b")
    eng = ServingEngine(cfg, slots=2, max_len=32, block_size=8, params=params)
    assert eng.mixed is False                # auto-off: not fully paged
    with pytest.raises(ValueError, match="fully paged"):
        ServingEngine(cfg, slots=2, max_len=32, block_size=8, params=params,
                      mixed=True)
    with pytest.raises(ValueError, match="mixed_budget"):
        cfg2, params2 = materialize("phi4-mini-3.8b")
        ServingEngine(cfg2, slots=2, max_len=32, block_size=8, params=params2,
                      mixed=True, mixed_budget=1)


# ---------------------------------------------------------------------------
# satellite bugfixes: clock domain + extras guard
# ---------------------------------------------------------------------------

class _Clock:
    """Deterministic strictly-increasing engine clock."""

    def __init__(self, dt=1e-3):
        self.t, self.dt = 0.0, dt

    def __call__(self):
        self.t += self.dt
        return self.t


def test_engine_dispatch_walls_single_clock_domain():
    """All dispatch walls come from the injectable engine clock: under a
    deterministic clock the metrics histograms, the stats time ledgers and
    the trace span durations agree exactly (regression: perf_counter-based
    walls drifted arbitrarily far from the engine-clock ledgers whenever a
    test or fault plan injected a clock)."""
    cfg, params = materialize("phi4-mini-3.8b")
    tracer = Tracer()
    eng = ServingEngine(cfg, slots=3, max_len=48, block_size=8, params=params,
                        tracer=tracer, clock=_Clock())
    eng.run(make_requests(cfg, _staggered(), seed=9))
    tracer.detach()                  # no GC hook outlives the test
    st = eng.stats
    assert st.mixed_dispatches > 0
    ledger = st.prefill_time + st.decode_time
    assert ledger > 0
    hist = sum(h.sum for name, h in eng.metrics.hists.items()
               if name.startswith("dispatch_"))
    assert hist == pytest.approx(ledger, rel=1e-9)
    spans = sum(ev.dur for ev in tracer.events() if ev.ph == "X" and ev.name
                in ("prefill-chunk", "decode", "horizon", "spec-horizon",
                    "mixed"))
    assert spans == pytest.approx(ledger, rel=1e-9)
    # a perf_counter wall under a fake 1 ms/tick clock would be real seconds
    # of jit+compute per dispatch — orders of magnitude off the tick budget
    n_dispatch = st.dispatches
    assert hist < 1.0 * n_dispatch           # every wall is a few fake ticks


def test_extras_chunk_guard_shared_by_submit_and_prefill():
    """One worst-case-replay bound (prompt + max_new - 1 ≤ chunk) guards the
    extras overlay on BOTH paths: submit() rejects up front, and the prefill
    path re-checks the same bound so a request that bypassed submit can
    never be half-served (regression: the paths used different lengths, so
    a request could pass admission then fail at recompute readmission)."""
    cfg, params = materialize("phi4-mini-3.8b")
    eng = ServingEngine(cfg, slots=2, max_len=64, block_size=8, params=params,
                        prefill_chunk=16)
    extras = {"patch_embeds": np.zeros((4, cfg.d_model), np.float32)}
    bad = Request(rid=0, prompt=np.arange(10, dtype=np.int32), max_new=8,
                  extras=extras)              # 10 + 8 - 1 = 17 > 16
    with pytest.raises(ValueError, match="prefill chunk"):
        eng.submit(bad)
    with pytest.raises(ValueError, match="prefill chunk"):
        eng._prefill_request(bad, 0.0, None)  # same bound, same rejection
    ok = Request(rid=1, prompt=np.arange(9, dtype=np.int32), max_new=8,
                 extras=extras)               # 9 + 8 - 1 = 16: boundary fits
    eng.submit(ok)
