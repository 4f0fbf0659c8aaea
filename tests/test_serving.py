"""Tests for repro.serving: block pool invariants, scheduler policy under a
randomized request stream, and end-to-end engine correctness.

The engine tests pin the strongest property available: the continuous-
batching path is *token-for-token* equal to (a) the static-batch loop on a
uniform workload and (b) an unconstrained run when preemption (swap AND
recompute) is forced by a tight block pool, and (c) a prefix-shared run is
token-identical to the unshared engine on shared-prompt streams.  The engine
parity families share one harness (tests/serving_harness.py).
"""
import numpy as np
import pytest

from serving_harness import (HORIZON_ARCHS, PARITY_ARCHS, materialize,
                             mixed_spec, run_workload, token_streams)

from repro.serving.blocks import BlockPool
from repro.serving.scheduler import PrefixCache, Request, RequestState, Scheduler


# ---------------------------------------------------------------------------
# block pool
# ---------------------------------------------------------------------------

def test_block_pool_alloc_free_reuse():
    pool = BlockPool(8, 4)
    assert pool.blocks_for(0) == 0
    assert pool.blocks_for(1) == 1
    assert pool.blocks_for(4) == 1
    assert pool.blocks_for(5) == 2
    a = pool.alloc(5)
    b = pool.alloc(3)
    assert pool.free_blocks == 0 and pool.used_blocks == 8
    assert pool.alloc(1) is None                     # exhausted: no change
    assert pool.free_blocks == 0
    assert len(set(a) | set(b)) == 8                 # disjoint ids
    pool.free(b)
    assert pool.free_blocks == 3
    c = pool.alloc(3)
    assert set(c) == set(b)                          # freed blocks are reused
    with pytest.raises(ValueError):
        pool.free([a[0], a[0]])                      # double free detected
    assert pool.alloc(4) is None                     # all-or-nothing

def test_block_pool_exhaustion_and_validation():
    pool = BlockPool(4, 2)
    assert pool.alloc(0) == []                       # empty alloc is a no-op
    with pytest.raises(ValueError):
        pool.alloc(-1)
    with pytest.raises(ValueError):
        BlockPool(-1, 2)
    with pytest.raises(ValueError):
        BlockPool(4, 0)
    a = pool.alloc(4)
    assert pool.alloc(1) is None                     # exhausted
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free([a[0]])                            # double free after bulk free


def test_block_pool_extend_to():
    pool = BlockPool(4, 4)
    table = []
    assert pool.extend_to(table, 0) and table == []
    assert pool.extend_to(table, 9)                  # 3 blocks
    assert len(table) == 3 and pool.free_blocks == 1
    assert pool.extend_to(table, 12) and len(table) == 3   # already covered
    # a grant beyond *total* pool capacity can never be satisfied: it must
    # fail loudly instead of silently reporting "try again later" (the
    # caller would preempt victims forever without ever meeting it)
    with pytest.raises(ValueError):
        pool.extend_to(table, 20)                    # needs 5, pool has 4
    assert len(table) == 3 and pool.free_blocks == 1 # no change on failure
    assert pool.extend_to(table, 16) and len(table) == 4
    # within capacity but currently short stays the quiet all-or-nothing False
    other: list = []
    assert not pool.extend_to(other, 8)
    assert other == []


def test_block_pool_randomized_invariants():
    rng = np.random.default_rng(0)
    pool = BlockPool(32, 2)
    live = []
    for _ in range(500):
        if live and rng.random() < 0.45:
            ids = live.pop(rng.integers(len(live)))
            pool.free(ids)
        else:
            ids = pool.alloc(int(rng.integers(1, 6)))
            if ids is not None:
                live.append(ids)
        held = [b for ids in live for b in ids]
        assert len(held) == len(set(held))                       # no aliasing
        assert pool.free_blocks + len(held) == pool.n_blocks     # conservation


# ---------------------------------------------------------------------------
# scheduler (no jax: pure policy)
# ---------------------------------------------------------------------------

def _mk_req(rid, plen, gen, arrival=0.0):
    return Request(rid=rid, prompt=np.zeros(plen, np.int32), max_new=gen,
                   arrival=arrival)


def _drive(req, steps=1):
    """Simulate the engine's per-step token bookkeeping for a running request."""
    for _ in range(steps):
        req.generated.append(0)


def test_scheduler_admission_and_completion():
    pool = BlockPool(64, 4)
    sched = Scheduler(2, pool, max_len=64)
    reqs = [_mk_req(i, 8, 4) for i in range(4)]
    for r in reqs:
        sched.submit(r)
    plan = sched.plan(now=0.0)
    assert [r.rid for r in plan.admit] == [0, 1]     # 2 slots
    assert all(r.state is RequestState.RUNNING for r in plan.admit)
    assert all(len(r.block_table) == pool.blocks_for(9) for r in plan.admit)
    # finish request 0 → its slot and blocks free; next plan admits request 2
    for r in plan.admit:
        _drive(r)                                    # first token from prefill
    reqs[0].generated.extend([0] * 3)
    sched.complete(reqs[0], now=1.0)
    assert reqs[0].state is RequestState.DONE and reqs[0].t_done == 1.0
    plan2 = sched.plan(now=1.0)
    assert [r.rid for r in plan2.admit] == [2]
    assert sum(len(r.block_table) for r in sched.running.values()) == pool.used_blocks


def test_scheduler_respects_arrival_times():
    pool = BlockPool(64, 4)
    sched = Scheduler(4, pool, max_len=64)
    sched.submit(_mk_req(0, 8, 4, arrival=0.0))
    sched.submit(_mk_req(1, 8, 4, arrival=10.0))
    plan = sched.plan(now=0.5)
    assert [r.rid for r in plan.admit] == [0]
    plan = sched.plan(now=10.5)
    assert [r.rid for r in plan.admit] == [1]


def test_scheduler_submit_validation():
    pool = BlockPool(3, 4)                           # 12-token device budget
    sched = Scheduler(2, pool, max_len=16)
    with pytest.raises(ValueError):
        sched.submit(_mk_req(0, 12, 8))              # 20 > max_len 16
    with pytest.raises(ValueError):
        sched.submit(_mk_req(1, 8, 8))               # 16 tokens = 4 blocks > 3
    sched.submit(_mk_req(2, 8, 4, arrival=0.0))      # 12 tokens = 3 blocks: fine


def test_scheduler_growth_preempts_youngest_and_recovers():
    # 2 slots, pool of 6 blocks × 4 tokens.  Two prompt-8 requests admit with
    # 3 blocks each (prompt + first decode row).  Once a request's cached
    # length hits 12 its next decode row needs a 4th block — the pool is
    # empty, so the younger request is preempted (recompute: no swap pool).
    pool = BlockPool(6, 4)
    sched = Scheduler(2, pool, max_len=24)
    r0, r1 = _mk_req(0, 8, 12, arrival=0.0), _mk_req(1, 8, 12, arrival=1.0)
    sched.submit(r0), sched.submit(r1)
    plan = sched.plan(now=2.0)
    assert len(plan.admit) == 2
    assert pool.free_blocks == 0
    _drive(r0, 5), _drive(r1, 5)                     # cached_len 12 → grow
    plan = sched.plan(now=3.0)
    assert [(p[0].rid, p[1]) for p in plan.preempt] == [(1, "recompute")]
    assert r1.state is RequestState.QUEUED and r1.block_table == []
    assert r1.n_preempt_recompute == 1
    assert len(r0.block_table) == 4                  # got its growth block
    # r1 keeps its generated tokens for recompute-readmission
    assert r1.n_generated == 5
    # a preemption step admits/resumes nothing (anti-thrash)
    assert not plan.admit and not plan.resume
    _drive(r0, 7)
    sched.complete(r0, now=4.0)
    plan = sched.plan(now=4.0)
    assert [r.rid for r in plan.admit] == [1]
    assert r1.state is RequestState.RUNNING


def test_scheduler_randomized_stream_conserves_blocks_and_finishes():
    rng = np.random.default_rng(42)
    pool = BlockPool(12, 4)
    sched = Scheduler(3, pool, max_len=32)
    reqs = [_mk_req(i, int(rng.integers(1, 17)), int(rng.integers(1, 13)),
                    arrival=float(rng.uniform(0, 5))) for i in range(25)]
    for r in reqs:
        sched.submit(r)
    done = []
    for step in range(2000):
        if not sched.has_work:
            break
        now = step * 0.1
        plan = sched.plan(now)
        for req in plan.admit:                       # engine: prefill emits token 1
            if req.n_generated == 0:
                req.generated.append(0)
            if req.done:                             # max_new == 1 retires here
                sched.complete(req, now)
                done.append(req)
        for slot in sorted(sched.running):
            req = sched.running[slot]
            req.generated.append(0)
            if req.done:
                sched.complete(req, now)
                done.append(req)
        # invariants every step
        held = [b for r in sched.running.values() for b in r.block_table]
        assert len(held) == len(set(held))
        assert pool.free_blocks + len(held) == pool.n_blocks
        for r in sched.running.values():
            assert len(r.block_table) >= pool.blocks_for(r.cached_len)
    assert sched.has_work is False
    assert sorted(r.rid for r in done) == list(range(25))
    assert all(r.n_generated >= r.max_new for r in done)
    assert pool.used_blocks == 0


def _admit_two(pool_blocks=64, bs=4, slots=2, max_len=64, gens=(12, 5)):
    """Two running requests (first token emitted), rest of the stream waiting."""
    pool = BlockPool(pool_blocks, bs)
    sched = Scheduler(slots, pool, max_len=max_len)
    reqs = [_mk_req(i, 8, g) for i, g in enumerate(gens)]
    for r in reqs:
        sched.submit(r)
    plan = sched.plan(now=0.0)
    for r in plan.admit:
        _drive(r)                                    # first token from prefill
    return pool, sched, reqs


def test_grant_horizon_completion_cap_and_preextension():
    # an *arrived* waiting request blocks the horizon at the earliest running
    # completion: min remaining = min(12-1, 5-1) = 4 → already a power of two
    pool, sched, reqs = _admit_two(gens=(12, 5, 4))
    h = sched.grant_horizon(16, now=0.0)
    assert h == 4
    r0, r1 = reqs[0], reqs[1]
    # tables pre-extended for the whole grant (capped at each budget)
    assert len(r0.block_table) >= pool.blocks_for(r0.cached_len + 4)
    assert len(r1.block_table) >= pool.blocks_for(r1.cached_len + 4)
    # with no pending work the grant runs to max_h, snapped to a power of two
    pool2, sched2, reqs2 = _admit_two(gens=(40, 37))
    assert sched2.grant_horizon(12, now=0.0) == 8    # 12 → 2^3
    # per-slot extension never exceeds the request's own budget
    pool3, sched3, reqs3 = _admit_two(gens=(40, 3))
    h3 = sched3.grant_horizon(16, now=0.0)
    assert h3 == 16
    big, small = reqs3
    assert len(big.block_table) == pool3.blocks_for(big.cached_len + 16)
    assert len(small.block_table) == pool3.blocks_for(small.cached_len + 2)


def test_grant_horizon_block_headroom_shrinks_grant():
    # 6 blocks × 4 tokens, two prompt-8 requests: 3 blocks each, pool empty.
    # cached_len 8 → h=4 fits the existing tables (12 rows = 3 blocks) but
    # h=8 would need a 4th block per slot → the grant halves instead of
    # preempting.
    pool, sched, reqs = _admit_two(pool_blocks=6, bs=4, max_len=24,
                                   gens=(12, 12))
    assert pool.free_blocks == 0
    assert sched.grant_horizon(8, now=0.0) == 4
    assert all(len(r.block_table) == 3 for r in reqs)


def test_grant_horizon_arrival_cap_and_empty():
    pool = BlockPool(64, 4)
    sched = Scheduler(2, pool, max_len=64)
    assert sched.grant_horizon(16, now=0.0) == 0     # nothing running
    sched.submit(_mk_req(0, 8, 30, arrival=0.0))
    sched.submit(_mk_req(1, 8, 30, arrival=5.0))     # future arrival
    for r in sched.plan(now=0.0).admit:
        _drive(r)
    # free slot + future arrival: cap ≈ steps until admission at 1s/step
    assert sched.grant_horizon(16, now=0.0, est_step_time=1.0) == 4  # 5+1→4
    # without an estimate the arrival cap is disabled
    assert sched.grant_horizon(16, now=0.0) == 16


def test_scheduler_table_version_tracks_mutations():
    pool, sched, reqs = _admit_two(gens=(12, 12))
    v = sched.table_version
    assert v > 0                                     # admissions bumped it
    sched.plan(now=1.0)                              # no growth needed yet
    assert sched.table_version == v
    _drive(reqs[0], 8)                               # cached_len 8 → 9: grow
    sched.plan(now=2.0)
    assert sched.table_version > v
    v = sched.table_version
    assert sched.grant_horizon(8, now=2.0) == 8      # pre-extends r1's table
    assert sched.table_version > v
    v = sched.table_version
    reqs[1].generated.extend([0] * 11)
    sched.complete(reqs[1], now=3.0)
    assert sched.table_version > v


# ---------------------------------------------------------------------------
# prefix cache: refcounted sharing + COW forks (pure bookkeeping, no jax)
# ---------------------------------------------------------------------------

def test_block_pool_refcounts_share_free_fork():
    pool = BlockPool(8, 4)
    a = pool.alloc(2)
    pool.share(a)                                     # second claim
    assert all(pool.refs(b) == 2 for b in a)
    pool.free(a)
    assert all(pool.refs(b) == 1 for b in a)          # still allocated
    assert pool.free_blocks == 6
    # COW fork: exclusive → in place; shared → fresh block, claim moved
    assert pool.fork(a[0]) == a[0]
    pool.share([a[0]])
    dst = pool.fork(a[0])
    assert dst not in a and pool.refs(dst) == 1 and pool.refs(a[0]) == 1
    pool.free(a)
    pool.free([dst])
    assert pool.free_blocks == 8 and pool.used_blocks == 0
    with pytest.raises(ValueError):
        pool.share([a[0]])                            # share of a free block
    with pytest.raises(ValueError):
        pool.fork(a[0])


def _sched_with_cache(n_blocks=16, bs=4, slots=4, max_len=64):
    pool = BlockPool(n_blocks, bs)
    cache = PrefixCache(pool, bs)
    sched = Scheduler(slots, pool, max_len=max_len, prefix_cache=cache)
    return pool, cache, sched


def _tok_req(rid, toks, gen, arrival=0.0):
    return Request(rid=rid, prompt=np.asarray(toks, np.int32), max_new=gen,
                   arrival=arrival)


def test_prefix_admission_aliases_blocks_and_allocates_marginal():
    pool, cache, sched = _sched_with_cache()
    base = list(range(11))                           # 2 full blocks + 3 partial
    r0 = _tok_req(0, base + [90], 4)                 # 12 tokens: 3 full blocks
    r1 = _tok_req(1, base + [91], 4)                 # shares 8 full + 3 partial
    sched.submit(r0), sched.submit(r1)
    plan = sched.plan(0.0)
    assert [r.rid for r in plan.admit] == [0, 1]
    g1 = plan.grants[1]
    assert 0 not in plan.grants                      # nothing resident for r0
    assert g1.shared_blocks == 2 and g1.start == 11  # 8 aliased + 3 via fork
    assert g1.fork is not None
    src, dst = g1.fork
    assert src == r0.block_table[2] and dst == r1.block_table[2]
    assert r1.block_table[:2] == r0.block_table[:2]  # aliased ids
    # refcounts: shared full blocks = r0 + r1 + cache; r0's partial = r0 + cache
    for b in r0.block_table[:2]:
        assert pool.refs(b) == 3
    assert pool.refs(src) == 2
    # marginal accounting: r1 allocated only its fork + unshared tail
    need = pool.blocks_for(r1.cached_len + 1)
    held = {b for r in (r0, r1) for b in r.block_table}
    assert len(held) == pool.blocks_for(r0.cached_len + 1) + need - 2
    # completion releases claims; the cache retains the prompt chain but the
    # decode-tail block (no prompt rows) goes back to the free list
    t0 = list(r0.block_table)
    r0.generated.extend([0] * 4)
    sched.complete(r0, 1.0)
    assert r0.block_table == []
    assert all(pool.refs(b) >= 1 for b in t0[:3])    # prompt blocks retained
    assert pool.refs(t0[3]) == 0


def test_prefix_cache_retains_after_completion_and_rematches():
    pool, cache, sched = _sched_with_cache()
    toks = list(range(10))
    r0 = _tok_req(0, toks, 2)
    sched.submit(r0)
    sched.plan(0.0)
    t0 = list(r0.block_table)
    r0.generated.extend([0, 0])
    sched.complete(r0, 1.0)
    assert len(cache) == 3                           # 2 full + 1 partial node
    assert pool.used_blocks == 3                     # retained by the cache
    r1 = _tok_req(1, toks, 2, arrival=2.0)           # identical prompt, later
    sched.submit(r1)
    plan = sched.plan(2.0)
    g = plan.grants[1]
    assert g.shared_blocks == 2 and g.start == 9     # limit = prompt_len - 1
    assert r1.block_table[:2] == t0[:2]
    assert g.fork is not None and g.fork[0] == t0[2]


def test_prefix_cache_evicts_lru_under_pressure():
    pool, cache, sched = _sched_with_cache(n_blocks=6, bs=4, slots=2, max_len=24)
    r0 = _tok_req(0, list(range(8)), 2)              # 2 full blocks + 1 row
    sched.submit(r0)
    sched.plan(0.0)
    r0.generated.extend([0, 0])
    sched.complete(r0, 1.0)
    assert pool.used_blocks == 2 and cache.reclaimable() == 2
    # a non-matching admission needs 6 blocks: the cache must give its 2 back
    r1 = _tok_req(1, [50 + i for i in range(20)], 4, arrival=2.0)
    sched.submit(r1)
    plan = sched.plan(2.0)
    assert [r.rid for r in plan.admit] == [1]
    # r0's chain was evicted to make room (unmatchable now); the cache holds
    # only r1's freshly registered 5-block prompt chain
    ids, p, src = cache.match(np.asarray(list(range(8)), np.int32), limit=7)
    assert ids == [] and p == 0
    assert len(cache) == 5
    held = set(r1.block_table)
    assert pool.free_blocks + len(held) == pool.n_blocks


def test_prefix_shared_block_never_freed_while_referenced():
    """Preempting (recompute) a request that shares prefix blocks must only
    drop its claims: the co-resident request still reads those blocks."""
    pool, cache, sched = _sched_with_cache(n_blocks=8, bs=4, slots=2, max_len=32)
    toks = list(range(8))
    r0 = _tok_req(0, toks, 16, arrival=0.0)
    r1 = _tok_req(1, toks, 16, arrival=0.1)
    sched.submit(r0), sched.submit(r1)
    plan = sched.plan(1.0)
    # limit = prompt_len - 1 = 7: one aliased full block + COW fork of the 2nd
    assert len(plan.admit) == 2 and plan.grants[1].shared_blocks == 1
    assert plan.grants[1].fork is not None
    shared = r0.block_table[:1]
    for r in plan.admit:
        r.generated.append(0)
    # drive both until the pool runs dry → youngest (r1) preempts
    for step in range(32):
        for r in list(sched.running.values()):
            r.generated.append(0)
        plan = sched.plan(2.0 + step)
        if plan.preempt:
            break
    assert plan.preempt and plan.preempt[0][0] is r1
    # r1's claims dropped, but the shared blocks still carry r0 + cache
    for b in shared:
        assert pool.refs(b) == 2
    held = {b for r in sched.running.values() for b in r.block_table}
    assert set(shared) <= held


def test_write_block_guard_detects_missed_cow_fork():
    """If a block the next decode writes is aliased by another table, plan()
    must fail loudly instead of corrupting the shared prefix."""
    pool, cache, sched = _sched_with_cache()
    r0 = _tok_req(0, list(range(9)), 4)
    sched.submit(r0)
    sched.plan(0.0)
    r0.generated.append(0)
    # simulate a missed COW fork: another table aliases r0's write block
    pool.share([r0.block_table[2]])
    with pytest.raises(RuntimeError, match="COW"):
        sched.plan(1.0)


def test_extend_to_capacity_overflow_fails_loudly_in_growth():
    """Regression: a mid-horizon grant whose target exceeds *total* pool
    capacity must raise out of extend_to, not silently under-deliver.  The
    scheduler path cannot reach it (submit validates), so drive extend_to
    the way grant_horizon does with a tight pool."""
    pool = BlockPool(3, 4)
    table = pool.alloc(3)
    with pytest.raises(ValueError, match="exceeds.*capacity|capacity"):
        pool.extend_to(table, 16)                    # 4 blocks > 3 total
    assert len(table) == 3                           # untouched
    # grant_horizon on a tight pool halves the grant instead of tripping it
    pool2 = BlockPool(6, 4)
    sched = Scheduler(2, pool2, max_len=24)
    for i, g in enumerate((12, 12)):
        sched.submit(_mk_req(i, 8, g))
    plan = sched.plan(0.0)
    for r in plan.admit:
        _drive(r)
    assert pool2.free_blocks == 0
    assert sched.grant_horizon(8, now=0.0) == 4      # headroom-capped, no raise


# ---------------------------------------------------------------------------
# paged store: block-table handoff swap (jax, no model)
# ---------------------------------------------------------------------------

def test_paged_store_block_handoff_roundtrip_and_ticket_reuse():
    """Pool-leaf swap is a block-to-block copy keyed by table ids: survive a
    device-block clobber after swap-out, restore into *different* device
    blocks, and reuse freed swap blocks for a second ticket without leakage."""
    import jax
    from repro.launch.steps import init_serving_caches
    from repro.models import registry
    from repro.serving.blocks import PagedKVStore
    cfg = registry.get_smoke("phi4-mini-3.8b")
    caches = init_serving_caches(cfg, batch=2, max_len=32, block_size=8,
                                 n_blocks=8)
    kp = caches[0]["attn"]["k_pool"]                 # [L, 9, 8, Hkv, D]
    assert kp.shape[1] == 9                          # 8 blocks + write-off
    caches[0]["attn"]["k_pool"] = kp.at[:, 1].set(1.0).at[:, 3].set(3.0)
    caches[0]["attn"]["pos"] = caches[0]["attn"]["pos"].at[:, 0].set(12)

    store = PagedKVStore(caches, n_blocks=4, block_size=8)
    sids = store.pool.alloc(2)
    ticket = store.swap_out(caches, slot=0, block_ids=sids, n_tokens=12,
                            dev_ids=[1, 3])
    # the freed device blocks get clobbered by other requests
    caches[0]["attn"]["k_pool"] = caches[0]["attn"]["k_pool"].at[:, 1].set(-7.0).at[:, 3].set(-7.0)
    # resume into a different slot AND different device blocks
    caches2 = store.swap_in(caches, slot=1, ticket=ticket, dev_ids=[0, 2])
    kp2 = np.asarray(caches2[0]["attn"]["k_pool"], np.float32)
    np.testing.assert_array_equal(kp2[:, 0], 1.0)
    np.testing.assert_array_equal(kp2[:, 2], 3.0)
    assert int(caches2[0]["attn"]["pos"][0, 1]) == 12   # side leaf followed
    # swap-block reuse: freed ids serve the next ticket with fresh contents
    store.pool.free(ticket.block_ids)
    sids2 = store.pool.alloc(2)
    assert set(sids2) == set(sids)
    caches2[0]["attn"]["k_pool"] = caches2[0]["attn"]["k_pool"].at[:, 5].set(5.0)
    t2 = store.swap_out(caches2, slot=0, block_ids=sids2, n_tokens=4,
                        dev_ids=[5])
    caches3 = store.swap_in(caches2, slot=0, ticket=t2, dev_ids=[7])
    np.testing.assert_array_equal(
        np.asarray(caches3[0]["attn"]["k_pool"], np.float32)[:, 7], 5.0)


def test_paged_store_requires_dev_ids_for_pool_leaves():
    from repro.launch.steps import init_serving_caches
    from repro.models import registry
    from repro.serving.blocks import PagedKVStore
    cfg = registry.get_smoke("phi4-mini-3.8b")
    caches = init_serving_caches(cfg, batch=1, max_len=16, block_size=8,
                                 n_blocks=4)
    store = PagedKVStore(caches, n_blocks=2, block_size=8)
    sids = store.pool.alloc(1)
    with pytest.raises(ValueError):
        store.swap_out(caches, 0, sids, 8)           # no dev_ids


# ---------------------------------------------------------------------------
# engine end-to-end (jax)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=PARITY_ARCHS)
def smoke_setup(request):
    return materialize(request.param)


def test_engine_parity_with_static_serve(smoke_setup):
    # prompt_len 8 keeps the comparison inside hymba's smoke window (8): the
    # static loop's one-shot prefill through a window-sized ring is lossy for
    # longer prompts (pre-existing), while the engine's headroom-padded ring
    # is exact — they legitimately diverge beyond the window.
    from repro.launch.serve import serve, serve_static
    cfg, params = smoke_setup
    g_eng, _ = serve(cfg, batch=3, prompt_len=8, gen=8, seed=0,
                     params=params, verbose=False)
    g_sta, _ = serve_static(cfg, batch=3, prompt_len=8, gen=8, seed=0,
                            params=params, verbose=False)
    np.testing.assert_array_equal(np.asarray(g_eng), np.asarray(g_sta))


def test_engine_chunked_prefill_matches_single_chunk(smoke_setup):
    from repro.launch.serve import serve
    cfg, params = smoke_setup
    g1, _ = serve(cfg, batch=2, prompt_len=16, gen=6, seed=0, params=params,
                  verbose=False)
    g2, _ = serve(cfg, batch=2, prompt_len=16, gen=6, seed=0, params=params,
                  verbose=False, prefill_chunk=4)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))


def _run_workload(cfg, params, n_blocks, swap_blocks):
    return run_workload(cfg, params, n_blocks=n_blocks, swap_blocks=swap_blocks)


def test_engine_continuous_batching_mixed_lengths(smoke_setup):
    cfg, params = smoke_setup
    toks, summary = _run_workload(cfg, params, n_blocks=None, swap_blocks=0)
    assert summary["preemptions"] == {"swap": 0, "recompute": 0}
    assert summary["generated_tokens"] == sum(len(v) for v in toks.values())
    # per-request ODIN attribution bills exactly the forward passes run:
    # prefill tokens + one decode pass per post-first generated token
    for rec in summary["requests"]:
        assert rec["odin"]["tokens"] == (rec["prefill_tokens"]
                                         + max(0, rec["generated_tokens"] - 1))
        assert rec["odin"]["energy_mj"] > 0
    assert 0 < summary["slot_occupancy"] <= 1


def test_engine_preemption_token_streams_identical(smoke_setup):
    cfg, params = smoke_setup
    base, s0 = _run_workload(cfg, params, n_blocks=None, swap_blocks=0)
    swap, s1 = _run_workload(cfg, params, n_blocks=8, swap_blocks=32)
    rec, s2 = _run_workload(cfg, params, n_blocks=8, swap_blocks=0)
    assert s1["preemptions"]["swap"] > 0              # pressure actually hit
    assert s2["preemptions"]["recompute"] > 0
    assert base == swap
    assert base == rec


def test_engine_vision_extras_survive_recompute_preemption():
    """Recompute replay of a vision-stub request re-prefills prompt+generated;
    pos3d must extend with the degenerate (t,t,t) decode positions instead of
    crashing on the original prompt-length table."""
    import jax
    from repro.models import lm as lm_mod, registry
    from repro.nn import module as nnmod
    from repro.serving import ServingEngine
    cfg = registry.get_smoke("qwen2-vl-2b")
    params = nnmod.materialize(lm_mod.param_spec(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def mk_reqs():
        out = []
        for i in range(5):
            plen = 16
            out.append(Request(
                rid=i, prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
                max_new=24,
                extras={"patch_embeds": np.zeros((4, cfg.d_model), np.float32),
                        "pos3d": np.repeat(np.arange(plen, dtype=np.int32)[:, None], 3, 1)}))
        return out

    def run(n_blocks):
        toks, s = run_workload(cfg, params, n_blocks=n_blocks,
                               requests=mk_reqs())
        return toks, s["preemptions"]["recompute"]

    rng = np.random.default_rng(0)
    full, _ = run(3 * 6)
    rng = np.random.default_rng(0)
    tight, n_rec = run(9)
    assert n_rec > 0
    assert full == tight


def test_engine_paged_vs_dense_cache_parity():
    """The paged physical block store must be token-for-token equal to the
    PR-1 dense live cache, with and without memory pressure, while holding
    measurably fewer device KV bytes on a tight pool."""
    cfg, params = materialize("phi4-mini-3.8b")
    dense, sd = run_workload(cfg, params, paged=False)
    paged, sp = run_workload(cfg, params, paged=True)
    tight, st = run_workload(cfg, params, paged=True, n_blocks=7)
    assert dense == paged == tight                   # 18 dense-equiv blocks → 7+1
    assert st["preemptions"]["recompute"] > 0        # pressure actually hit
    assert st["kv_cache_bytes"] < sd["kv_cache_bytes"] / 2


def test_engine_sampling_deterministic_per_seed():
    """temperature/top-k decode: same seed reproduces the stream, different
    seeds (and greedy) diverge; greedy stays the default contract."""
    import jax
    from repro.models import lm as lm_mod, registry
    from repro.nn import module as nnmod
    from repro.serving import Request, ServingEngine
    cfg = registry.get_smoke("phi4-mini-3.8b")
    params = nnmod.materialize(lm_mod.param_spec(cfg), jax.random.PRNGKey(0))

    def run(temperature, top_k, sample_seed=0):
        eng = ServingEngine(cfg, slots=2, max_len=32, block_size=8,
                            params=params, temperature=temperature,
                            top_k=top_k, sample_seed=sample_seed)
        reqs = [Request(rid=i, prompt=np.arange(8, dtype=np.int32) + i,
                        max_new=6) for i in range(3)]
        eng.run(reqs)
        return {r.rid: [int(np.asarray(t)) for t in r.generated] for r in reqs}

    greedy = run(0.0, 0)
    s1 = run(1.0, 5)
    assert run(1.0, 5) == s1                         # deterministic per seed
    assert s1 != greedy
    assert run(1.0, 5, sample_seed=7) != s1


def test_sample_tokens_top_k_membership_and_greedy():
    import jax
    import jax.numpy as jnp
    from repro.launch.steps import _sample_tokens
    from repro.models import registry
    cfg = registry.get_smoke("phi4-mini-3.8b")
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 1, 32)), jnp.float32)
    greedy = _sample_tokens(logits, cfg, None, 0.0, 0)
    np.testing.assert_array_equal(
        np.asarray(greedy)[:, 0], np.argmax(np.asarray(logits)[:, 0], -1))
    # traced temperature 0 with a key still selects the argmax
    z = _sample_tokens(logits, cfg, jax.random.PRNGKey(0), jnp.float32(0.0), 5)
    np.testing.assert_array_equal(np.asarray(z), np.asarray(greedy))
    top3 = np.argsort(np.asarray(logits)[:, 0], -1)[:, -3:]
    for i in range(50):
        s = np.asarray(_sample_tokens(logits, cfg, jax.random.PRNGKey(i),
                                      jnp.float32(1.0), 3))[:, 0]
        for b in range(4):
            assert s[b] in top3[b], (b, s[b], top3[b])


# ---------------------------------------------------------------------------
# horizon-batched decode (jax)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=HORIZON_ARCHS)
def horizon_setup(request):
    return materialize(request.param)


def _run_horizon(cfg, params, horizon, **kwargs):
    return run_workload(cfg, params, horizon=horizon, **kwargs)


def test_engine_horizon_token_parity_all_families(horizon_setup):
    """H>1 must be token-for-token identical to H=1 (greedy), with mid-horizon
    budget freezes exercised by the short gen bucket, while actually
    amortizing dispatches."""
    cfg, params = horizon_setup
    base, s1 = _run_horizon(cfg, params, 1)
    fused, s8 = _run_horizon(cfg, params, 8)
    assert base == fused
    assert s8["decode_dispatches"] < s1["decode_dispatches"]
    assert s8["tokens_per_dispatch"] > s1["tokens_per_dispatch"]
    assert s8["decode_tokens"] == s1["decode_tokens"]


def test_engine_horizon_sampled_parity():
    """Sampled decode folds the *global* step counter into the key, so a
    horizon run reproduces the single-step stream when the slot schedule
    matches (all-arrived workload, no preemption)."""
    cfg, params = materialize("phi4-mini-3.8b")
    base, _ = _run_horizon(cfg, params, 1, temperature=1.0, top_k=5)
    fused, _ = _run_horizon(cfg, params, 8, temperature=1.0, top_k=5)
    greedy, _ = _run_horizon(cfg, params, 8)
    assert base == fused
    assert base != greedy


def test_engine_horizon_eos_freeze_mid_horizon():
    """EOS must freeze a slot mid-horizon on-device exactly where the host
    path stops it: pick a token that actually occurs mid-stream in the
    baseline, declare it EOS, and require identical truncated streams."""
    cfg, params = materialize("phi4-mini-3.8b")
    base, _ = _run_horizon(cfg, params, 1)
    rid = idx = eos = None
    for r, stream in sorted(base.items()):   # first token not repeated earlier
        for i in range(2, len(stream) - 1):
            v = stream[i][0]
            if all(s[0] != v for s in stream[:i]):
                rid, idx, eos = r, i, v
                break
        if eos is not None:
            break
    assert eos is not None, "baseline streams have no usable mid-stream token"
    h1, _ = _run_horizon(cfg, params, 1, eos_id=eos)
    h8, _ = _run_horizon(cfg, params, 8, eos_id=eos)
    assert h1 == h8
    assert len(h1[rid]) == idx + 1           # truncated at the EOS token
    assert h1[rid][-1][0] == eos
    assert len(h1[rid]) < len(base[rid])
    # the non-EOS prefix is unchanged
    assert base[rid][:idx + 1] == h1[rid]


def test_engine_horizon_preemption_at_boundary(smoke_setup):
    """A tight pool under a horizon engine: grants shrink to the block
    headroom, preemption (swap AND recompute) lands on horizon boundaries via
    plan(), and greedy token streams stay identical to the unconstrained
    run."""
    cfg, params = smoke_setup
    base, _ = _run_horizon(cfg, params, 1)
    swap, s_sw = _run_horizon(cfg, params, 8, n_blocks=8, swap_blocks=32)
    rec, s_rc = _run_horizon(cfg, params, 8, n_blocks=8, swap_blocks=0)
    assert s_sw["preemptions"]["swap"] > 0
    assert s_rc["preemptions"]["recompute"] > 0
    assert base == swap
    assert base == rec


def test_engine_horizon_timestamps_use_engine_clock():
    """Interpolated horizon timestamps must come from the *engine* clock, so
    an injected deterministic clock yields monotone per-request times and
    non-negative TPOT (regression: mixing in perf_counter spans produced
    timestamps before TTFT under a fake clock)."""
    import itertools
    import jax
    from repro.models import lm as lm_mod, registry
    from repro.nn import module as nnmod
    from repro.serving import Request, ServingEngine
    cfg = registry.get_smoke("phi4-mini-3.8b")
    params = nnmod.materialize(lm_mod.param_spec(cfg), jax.random.PRNGKey(0))
    fake = itertools.count()
    seen = {}
    eng = ServingEngine(cfg, slots=2, max_len=32, block_size=8, params=params,
                        horizon=8, clock=lambda: float(next(fake)),
                        on_token=lambda r, t, now: seen.setdefault(r.rid, []).append(now))
    reqs = [Request(rid=i, prompt=np.arange(8, dtype=np.int32) + i, max_new=6)
            for i in range(3)]
    summary = eng.run(reqs)
    for r in reqs:
        ts = seen[r.rid]
        assert ts == sorted(ts)
        assert r.t_first_token >= 0 and r.t_done >= ts[-1]
    for rec in summary["requests"]:
        assert rec["ttft_s"] >= 0
        assert rec["tpot_s"] is None or rec["tpot_s"] >= 0


def test_engine_horizon_dispatch_observables():
    cfg, params = materialize("phi4-mini-3.8b")
    _, s = _run_horizon(cfg, params, 4)
    assert s["decode_dispatches"] > 0
    assert s["decode_steps"] > s["decode_dispatches"]     # amortization real
    assert s["host_syncs"] <= s["dispatches"]
    assert s["tokens_per_dispatch"] == pytest.approx(
        s["decode_tokens"] / s["decode_dispatches"])


# ---------------------------------------------------------------------------
# prefix sharing end-to-end (jax)
# ---------------------------------------------------------------------------

def _shared_spec(**kw):
    return mixed_spec(n_requests=6, shared_prefix=kw.pop("shared_prefix", 16),
                      prompt_buckets=(8, 16), gen_buckets=(4, 24), **kw)


# phi4 pins the single-codebook paged family; musicgen pins the multi-
# codebook [K, S] prompt hashing + token-block layout.
@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "musicgen-medium"])
def test_engine_prefix_sharing_token_parity_and_savings(arch):
    """Shared-prompt streams must be token-identical with sharing on vs off,
    while actually skipping prefill work and referencing fewer blocks."""
    cfg, params = materialize(arch)
    base, sb = run_workload(cfg, params, max_len=64, spec=_shared_spec(),
                            prefix_sharing=False)
    shared, ss = run_workload(cfg, params, max_len=64, spec=_shared_spec(),
                              prefix_sharing=True)
    assert base == shared
    assert ss["prefix"]["hit_tokens"] > 0
    assert ss["prefix"]["shared_blocks"] > 0
    assert ss["prefill_tokens"] < sb["prefill_tokens"]
    assert (ss["prefix"]["mean_referenced_blocks"]
            < sb["prefix"]["mean_referenced_blocks"])
    # attribution bills only the forwards actually run: shared rows are free
    for rec in ss["requests"]:
        assert rec["odin"]["tokens"] == (rec["prefill_tokens"]
                                         + max(0, rec["generated_tokens"] - 1))


def test_engine_prefix_cow_fork_non_aligned_prefix():
    """Prompts sharing a non-block-aligned prefix take the COW-fork path:
    the partially matched block is copied before the tail overwrites it."""
    cfg, params = materialize("phi4-mini-3.8b")
    spec = _shared_spec(shared_prefix=21, share_groups=2)
    base, _ = run_workload(cfg, params, max_len=64, spec=spec, prefix_sharing=False)
    shared, ss = run_workload(cfg, params, max_len=64, spec=spec, prefix_sharing=True)
    assert base == shared
    assert ss["prefix"]["cow_forks"] > 0
    assert ss["prefix"]["hit_tokens"] > 0


def test_engine_prefix_sharing_preemption_parity(smoke_setup):
    """Sharing + preemption (swap AND recompute) of slots holding shared
    blocks: token streams still match the unconstrained unshared run.  On
    non-fully-paged families (hymba ring+SSM, deepseek MLA) sharing auto-
    disables and this degenerates to the plain preemption parity check."""
    cfg, params = smoke_setup
    spec = _shared_spec()
    base, _ = run_workload(cfg, params, max_len=64, spec=spec, prefix_sharing=False)
    swap, s_sw = run_workload(cfg, params, max_len=64, spec=spec, n_blocks=11,
                              swap_blocks=32)
    rec, s_rc = run_workload(cfg, params, max_len=64, spec=spec, n_blocks=11)
    assert s_sw["preemptions"]["swap"] > 0
    assert s_rc["preemptions"]["recompute"] > 0
    assert base == swap
    assert base == rec


def test_engine_prefix_sharing_horizon_parity():
    """Prefix sharing composes with horizon-batched decode: pre-extended
    tables append exclusive blocks after the shared prefix."""
    cfg, params = materialize("phi4-mini-3.8b")
    base, _ = run_workload(cfg, params, max_len=64, spec=_shared_spec(),
                           prefix_sharing=False)
    fused, s8 = run_workload(cfg, params, max_len=64, spec=_shared_spec(), horizon=8)
    assert base == fused
    assert s8["prefix"]["hit_tokens"] > 0
    assert s8["tokens_per_dispatch"] > 1.0


def test_engine_prefix_cache_retained_across_completion():
    """System-prompt caching: a request arriving after every sharer finished
    still hits the resident chain (the cache's claim outlives the request)."""
    import itertools
    from repro.serving import Request, ServingEngine
    cfg, params = materialize("phi4-mini-3.8b")
    prompt = (np.arange(20, dtype=np.int32) * 7 + 3) % cfg.vocab
    fake = itertools.count()
    eng = ServingEngine(cfg, slots=2, max_len=32, block_size=8, params=params,
                        clock=lambda: float(next(fake)))
    assert eng.prefix_sharing                        # auto-on: fully paged
    reqs = [Request(rid=0, prompt=prompt, max_new=4, arrival=0.0),
            Request(rid=1, prompt=prompt.copy(), max_new=4, arrival=50.0)]
    s = eng.run(reqs)
    assert s["prefix"]["hit_tokens"] == 19           # prompt_len - 1 (16 + 3)
    assert s["prefix"]["cow_forks"] == 1
    assert token_streams(reqs)[0] == token_streams(reqs)[1]


def test_engine_prefix_sharing_eligibility_and_extras_bypass():
    """Non-fully-paged families auto-disable sharing (forcing it raises);
    extras-carrying requests never match or register even when sharing is
    on (their KV is not token-determined)."""
    import jax
    from repro.models import lm as lm_mod, registry
    from repro.nn import module as nnmod
    from repro.serving import Request, ServingEngine
    for arch in ("hymba-1.5b", "deepseek-v3-671b", "xlstm-350m"):
        cfg, params = materialize(arch)
        eng = ServingEngine(cfg, slots=2, max_len=32, block_size=8,
                            params=params)
        assert not eng.prefix_sharing
        with pytest.raises(ValueError, match="fully paged"):
            ServingEngine(cfg, slots=2, max_len=32, block_size=8,
                          params=params, prefix_sharing=True)
    cfg = registry.get_smoke("qwen2-vl-2b")
    params = nnmod.materialize(lm_mod.param_spec(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, 16).astype(np.int32)
    reqs = [Request(rid=i, prompt=prompt.copy(), max_new=4,
                    extras={"patch_embeds": np.full((4, cfg.d_model), i, np.float32),
                            "pos3d": np.repeat(np.arange(16, dtype=np.int32)[:, None], 3, 1)})
            for i in range(3)]
    toks, s = run_workload(cfg, params, slots=3, max_len=32, requests=reqs)
    assert s["prefix"]["hit_tokens"] == 0            # same tokens, different KV
    # different patch embeds ⇒ the streams must NOT be forced equal by sharing
    assert len(toks[0]) == len(toks[1]) == 4


def test_engine_streaming_callback_and_order(smoke_setup):
    from repro.serving import Request, ServingEngine
    cfg, params = smoke_setup
    seen = {}
    eng = ServingEngine(cfg, slots=2, max_len=32, block_size=8, params=params,
                        on_token=lambda r, t, now: seen.setdefault(r.rid, []).append(int(np.asarray(t))))
    reqs = [Request(rid=i, prompt=np.arange(8, dtype=np.int32) + i, max_new=5)
            for i in range(3)]
    eng.run(reqs)
    for r in reqs:
        assert seen[r.rid] == [int(np.asarray(t)) for t in r.generated]
        assert len(seen[r.rid]) == 5


# ---------------------------------------------------------------------------
# n-gram self-speculative decode (scheduler accounting + engine end-to-end)
# ---------------------------------------------------------------------------

# one arch per speculable cache family: paged GQA, MoE-over-paged-GQA, and
# MLA's dense latent cache (spec rides the generic S>1 decode path there)
SPEC_ARCHS = ["phi4-mini-3.8b", "qwen3-moe-235b-a22b", "deepseek-v3-671b"]


def test_ngram_propose_matches_and_fallback():
    import jax.numpy as jnp
    from repro.launch.steps import ngram_propose
    hist = jnp.asarray([
        # bigram (7, 8) seen earlier, followed by 9, 1 → draft [9, 1]
        [-1, -1, 7, 8, 9, 1, 5, 7, 8],
        # no earlier match → repeat the last token
        [-1, -1, -1, 1, 2, 3, 4, 5, 6],
        # most recent match wins: (7, 8) at j=0 and j=3 → follow j=3
        [7, 8, 3, 7, 8, 5, 0, 7, 8],
        # padding never matches real tokens, and boundary drafts clamp ≥ 0
        [-1, -1, -1, -1, -1, -1, -1, 5, 5],
    ], jnp.int32)
    draft = np.asarray(ngram_propose(hist, K=2, n=2))
    np.testing.assert_array_equal(draft[0], [9, 1])
    np.testing.assert_array_equal(draft[1], [6, 6])
    np.testing.assert_array_equal(draft[2], [5, 0])
    assert (draft >= 0).all()


def test_speculable_gates_families():
    from repro.launch.steps import speculable
    from repro.models import registry
    assert speculable(registry.get_smoke("phi4-mini-3.8b"))
    assert speculable(registry.get_smoke("qwen3-moe-235b-a22b"))
    assert speculable(registry.get_smoke("deepseek-v3-671b"))
    assert not speculable(registry.get_smoke("hymba-1.5b"))      # SSM state
    assert not speculable(registry.get_smoke("xlstm-350m"))      # recurrent
    assert not speculable(registry.get_smoke("musicgen-medium")) # codebooks


def test_engine_spec_rejects_unsupported_configs():
    from repro.models import registry
    from repro.serving import ServingEngine
    for arch in ("hymba-1.5b", "xlstm-350m", "musicgen-medium"):
        with pytest.raises(ValueError, match="spec_ngram|recurrent|codebook"):
            ServingEngine(registry.get_smoke(arch), slots=2, max_len=32,
                          block_size=8, spec_ngram=2)
    cfg = registry.get_smoke("phi4-mini-3.8b")
    with pytest.raises(ValueError, match="greedy"):
        ServingEngine(cfg, slots=2, max_len=32, block_size=8, spec_ngram=2,
                      temperature=0.7)
    with pytest.raises(ValueError, match="spec_hist"):
        ServingEngine(cfg, slots=2, max_len=32, block_size=8, spec_ngram=4,
                      spec_hist=5)


def test_grant_horizon_spec_worst_case_preextension_and_fallback():
    """Speculative grants must pre-extend for the worst case — every inner
    step writes K+1 rows, and a budget-frozen slot still wrote K rows past
    its last accepted token — and must return 0 (plain-decode fallback)
    when the pool cannot cover even one verify tile."""
    pool, sched, reqs = _admit_two(gens=(40, 37))
    h = sched.grant_horizon(4, now=0.0, spec_k=3)
    assert h == 4
    for r in reqs:
        rows = min(sched.max_len, r.cached_len + min(4 * 4, r.remaining + 3))
        assert len(r.block_table) == pool.blocks_for(rows)
    # completion cap counts accept-aware steps: remaining 4 at K=3 can finish
    # in one inner step → grant 1 even with arrived work queued
    pool2, sched2, reqs2 = _admit_two(gens=(5, 5, 8))
    assert sched2.grant_horizon(16, now=0.0, spec_k=3) == 1
    # pool too tight for even one K+1-row tile → 0, single-step fallback
    pool3 = BlockPool(6, 4)
    sched3 = Scheduler(2, pool3, max_len=24, write_span=4)
    for i in range(2):
        sched3.submit(_mk_req(i, 8, 12))
    for r in sched3.plan(0.0).admit:
        _drive(r)
    for r in sched3.running.values():
        _drive(r, 2)                 # cached_len 10: the verify tile (rows
    sched3.plan(0.5)                 # 10..13) crosses into a 4th block
    assert pool3.free_blocks == 0                    # 3 blocks each
    assert sched3.grant_horizon(1, now=0.0, spec_k=3) == 0
    # spec-off grants are unchanged by the spec machinery
    assert sched3.grant_horizon(1, now=0.0) == 1


def test_preempt_keeps_shared_prefix_claims_and_resume_reattaches():
    """Sharing-aware swap: blocks the prefix cache (or a co-reader) still
    holds keep the swapped request's claim instead of round-tripping through
    the swap tier; resume re-attaches them and allocates only the exclusive
    suffix."""
    pool = BlockPool(16, 4)
    cache = PrefixCache(pool, 4)
    swap = BlockPool(8, 4)
    sched = Scheduler(1, pool, max_len=32, swap_pool=swap, prefix_cache=cache)
    toks = np.arange(12, dtype=np.int32)
    req = Request(rid=0, prompt=toks, max_new=8)
    sched.submit(req)
    for r in sched.plan(0.0).admit:
        _drive(r)                                    # first token from prefill
    _drive(req, 2)                                   # cached_len 14: block 3 live
    assert len(req.block_table) == 4
    plan = sched.plan(1.0)
    sched._preempt(req, plan)
    # prompt blocks 0..2 are cache-held (refs 2 before free) → kept; the
    # tail block (rows 12..13, decode-written) is exclusive → swapped
    assert req.state.value == "swapped"
    kept_ids = list(req.kept_blocks)
    assert len(kept_ids) == 3
    assert all(pool.refs(b) == 2 for b in kept_ids)
    assert swap.used_blocks == 1                     # only the suffix block
    # resume: kept blocks lead the new table, only the suffix is allocated
    plan2 = sched.plan(2.0)
    assert plan2.resume == [req]
    assert req.block_table[:3] == kept_ids
    assert req.kept_blocks == []
    assert len(req.block_table) == 4


def test_swap_ticket_skip_roundtrip():
    """A ticket with skip_blocks restores into table rows skip onward and
    never touches the retained leading blocks."""
    from repro.launch.steps import init_serving_caches
    from repro.models import registry
    from repro.serving.blocks import PagedKVStore
    cfg = registry.get_smoke("phi4-mini-3.8b")
    caches = init_serving_caches(cfg, batch=2, max_len=32, block_size=8,
                                 n_blocks=8)
    kp = caches[0]["attn"]["k_pool"]
    caches[0]["attn"]["k_pool"] = kp.at[:, 1].set(1.0).at[:, 3].set(3.0)
    caches[0]["attn"]["pos"] = caches[0]["attn"]["pos"].at[:, 0].set(12)
    store = PagedKVStore(caches, n_blocks=4, block_size=8)
    sids = store.pool.alloc(1)                       # suffix only
    ticket = store.swap_out(caches, slot=0, block_ids=sids, n_tokens=12,
                            dev_ids=[1, 3], skip=1)
    assert ticket.skip_blocks == 1
    # block 1 was retained (never copied): clobber only block 3
    caches[0]["attn"]["k_pool"] = caches[0]["attn"]["k_pool"].at[:, 3].set(-7.0)
    caches2 = store.swap_in(caches, slot=0, ticket=ticket, dev_ids=[1, 6])
    kp2 = np.asarray(caches2[0]["attn"]["k_pool"], np.float32)
    np.testing.assert_array_equal(kp2[:, 1], 1.0)    # retained block intact
    np.testing.assert_array_equal(kp2[:, 6], 3.0)    # suffix restored


@pytest.fixture(scope="module", params=SPEC_ARCHS)
def spec_setup(request):
    return materialize(request.param)


def test_engine_spec_token_parity_all_families(spec_setup):
    """Greedy spec-on streams must be token-identical to spec-off by
    construction (every emitted token is an argmax), across the paged-GQA,
    MoE and MLA cache families, while drafting real work."""
    cfg, params = spec_setup
    base, s0 = run_workload(cfg, params)
    for K in (2, 4):
        spec, s1 = run_workload(cfg, params, spec_ngram=K)
        assert base == spec, f"spec K={K} diverged"
        assert s1["decode_tokens"] == s0["decode_tokens"]
        assert s1["speculation"]["drafted"] > 0
        assert 0 <= s1["speculation"]["accepted"] <= s1["speculation"]["drafted"]


def test_engine_spec_fuses_into_horizon_scan():
    """spec_ngram composes with horizon>1: one dispatch runs h inner
    draft→verify steps; parity holds and dispatches drop vs plain h=1."""
    cfg, params = materialize("phi4-mini-3.8b")
    base, s0 = run_workload(cfg, params)
    spec, s1 = run_workload(cfg, params, spec_ngram=2, horizon=8)
    assert base == spec
    assert s1["decode_dispatches"] < s0["decode_dispatches"]
    assert s1["tokens_per_dispatch"] > s0["tokens_per_dispatch"]


def test_engine_spec_preemption_parity():
    """Tight pools under speculation: worst-case write-span budgeting plus
    swap/recompute preemption must keep streams identical."""
    cfg, params = materialize("phi4-mini-3.8b")
    base, _ = run_workload(cfg, params)
    swap, s_sw = run_workload(cfg, params, spec_ngram=4, n_blocks=8,
                              swap_blocks=32)
    rec, s_rc = run_workload(cfg, params, spec_ngram=4, n_blocks=8)
    assert s_sw["preemptions"]["swap"] > 0
    assert s_rc["preemptions"]["recompute"] > 0
    assert base == swap
    assert base == rec


def test_engine_spec_shared_prefix_parity_and_swap_skip():
    """Speculation over prefix-shared streams: parity with the unshared
    spec-off run, and sharing-aware swap tickets actually skip resident
    blocks under pressure."""
    cfg, params = materialize("phi4-mini-3.8b")
    wspec = mixed_spec(n_requests=6, shared_prefix=24, prompt_buckets=(8, 16),
                       gen_buckets=(4, 16))
    base, _ = run_workload(cfg, params, max_len=64, spec=wspec,
                           prefix_sharing=False)
    spec, s1 = run_workload(cfg, params, max_len=64, spec=wspec,
                            prefix_sharing=True, spec_ngram=4)
    assert base == spec
    pressured, s2 = run_workload(cfg, params, max_len=64, spec=wspec,
                                 prefix_sharing=True, spec_ngram=4,
                                 n_blocks=12, swap_blocks=32)
    assert base == pressured
    if s2["preemptions"]["swap"]:
        assert s2["prefix"]["swap_skipped_blocks"] > 0


def test_engine_spec_eos_parity():
    """EOS inside an accepted run must truncate exactly where the plain
    engine stops (on-device accept truncation + host re-check agree)."""
    cfg, params = materialize("phi4-mini-3.8b")
    base, _ = run_workload(cfg, params)
    rid = idx = eos = None
    for r, stream in sorted(base.items()):
        for i in range(2, len(stream) - 1):
            v = stream[i][0]
            if all(s[0] != v for s in stream[:i]):
                rid, idx, eos = r, i, v
                break
        if eos is not None:
            break
    assert eos is not None
    b_eos, _ = run_workload(cfg, params, eos_id=eos)
    s_eos, _ = run_workload(cfg, params, eos_id=eos, spec_ngram=4)
    assert b_eos == s_eos
    assert len(s_eos[rid]) == idx + 1 and s_eos[rid][-1][0] == eos


def test_engine_spec_rollback_never_below_committed_length():
    """Per-slot KV lengths advance by the accepted count only: stepping the
    engine manually, a slot's length never decreases while the same request
    holds it, never grows past h·(K+1) per dispatch, and stays covered by
    its block table."""
    import jax
    from repro.models import lm as lm_mod, registry
    from repro.nn import module as nnmod
    from repro.serving import Request, ServingEngine
    cfg = registry.get_smoke("phi4-mini-3.8b")
    params = nnmod.materialize(lm_mod.param_spec(cfg), jax.random.PRNGKey(0))
    K, H = 3, 4
    eng = ServingEngine(cfg, slots=3, max_len=48, block_size=8, params=params,
                        spec_ngram=K, horizon=H)
    rng = np.random.default_rng(0)
    pat = rng.integers(0, cfg.vocab, 4, dtype=np.int32)
    reqs = [Request(rid=i, prompt=np.tile(pat, 3), max_new=24)
            for i in range(4)]
    for r in reqs:
        eng.submit(r)
    guard = 0
    while eng.sched.has_work:
        before = dict(eng.sched.running)
        len_before = eng._slot_len.copy()
        eng.step()
        for s, req in before.items():
            if eng.sched.running.get(s) is req and req.slot == s:
                grew = int(eng._slot_len[s]) - int(len_before[s])
                assert 0 <= grew <= H * (K + 1)
                assert len(req.block_table) * 8 >= req.cached_len
        guard += 1
        assert guard < 500
    assert all(r.n_generated == 24 for r in reqs)


def test_engine_spec_accepts_on_repetitive_stream():
    """The observables must show real speculation wins on repetition-heavy
    traffic: positive accept rate and more tokens per dispatch than the
    spec-off engine at the same horizon."""
    import dataclasses
    from repro.serving import SCENARIOS, make_requests
    cfg, params = materialize("phi4-mini-3.8b")
    wspec = dataclasses.replace(SCENARIOS["repetitive"], n_requests=4,
                                rate=1e9, gen_buckets=(96,))
    base, s0 = run_workload(cfg, params, slots=3, max_len=144, block_size=16,
                            spec=wspec, horizon=4)
    spec, s1 = run_workload(cfg, params, slots=3, max_len=144, block_size=16,
                            spec=wspec, horizon=4, spec_ngram=4)
    assert base == spec
    assert s1["speculation"]["accept_rate"] > 0.2
    assert s1["tokens_per_dispatch"] > s0["tokens_per_dispatch"]
    assert s1["decode_dispatches"] < s0["decode_dispatches"]


def test_engine_jit_cache_lru_bounded_with_evictions():
    """The fused-executable cache must stay bounded across horizon×spec
    grant combinations, count its evictions, and keep streams identical."""
    cfg, params = materialize("phi4-mini-3.8b")
    base, s0 = run_workload(cfg, params, horizon=8, spec_ngram=2)
    tight, s1 = run_workload(cfg, params, horizon=8, spec_ngram=2,
                             jit_cache=1)
    assert base == tight
    assert s0["jit_evictions"] == 0
    assert s1["jit_evictions"] > 0


def test_engine_spec_history_stays_aligned_including_fallback():
    """The per-slot draft history must track prompt+generated exactly at
    every step — including plain-decode fallback steps when the pool cannot
    cover a verify tile (regression: the fallback emitted a token without
    shifting it into the ring, silently collapsing accept rates)."""
    import jax
    from repro.models import lm as lm_mod, registry
    from repro.nn import module as nnmod
    from repro.serving import Request, ServingEngine
    cfg = registry.get_smoke("phi4-mini-3.8b")
    params = nnmod.materialize(lm_mod.param_spec(cfg), jax.random.PRNGKey(0))
    # 7 blocks × bs 8 over 2 slots of max_len 48: tight enough that spec
    # grants intermittently fail and fall back to single steps
    eng = ServingEngine(cfg, slots=2, max_len=48, block_size=8, params=params,
                        spec_ngram=3, spec_hist=16, n_blocks=7)
    grants = []
    orig = eng.sched.grant_horizon
    eng.sched.grant_horizon = lambda *a, **kw: grants.append(orig(*a, **kw)) or grants[-1]
    reqs = [Request(rid=i, prompt=np.arange(16, dtype=np.int32) + i, max_new=20)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    guard = 0
    while eng.sched.has_work:
        eng.step()
        for slot, req in eng.sched.running.items():
            if req.prefilling:
                continue        # a slot's history is seeded when its prompt ends
            ctx = np.concatenate([np.asarray(req.replay_tokens()).ravel(),
                                  np.ravel(req.generated[-1])])
            row = np.asarray(eng._hist[slot])
            n = min(len(ctx), len(row))
            np.testing.assert_array_equal(row[-n:], ctx[-n:].astype(np.int32))
        guard += 1
        assert guard < 400
    assert 0 in grants                   # the fallback path actually ran
    assert any(g >= 1 for g in grants)   # and so did real spec dispatches
