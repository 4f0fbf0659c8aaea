"""Async streaming front door: backpressure, tenant QoS, failure semantics.

Chaos-client suite for :class:`repro.serving.frontdoor.FrontDoor`: stream
parity against the synchronous engine, typed admission rejections
(queue-full / degradation / tenant quota / draining), disconnect-cancel,
slow readers, deadline expiry, graceful shutdown mid-burst, and heartbeats.
Every engine test asserts the no-leak invariants: all slots free, pool
blocks down to prefix-cache-held, and every request in exactly one
terminal state.

No pytest-asyncio in the image: async tests are plain functions driving
``asyncio.run`` themselves.
"""
import asyncio

import numpy as np
import pytest

from serving_harness import materialize, mixed_spec, token_streams
from repro.serving import (FrontDoor, Overloaded, Request, ServingEngine,
                           ShuttingDown, TokenBucket, make_requests)


@pytest.fixture(scope="module")
def phi4_setup():
    return materialize("phi4-mini-3.8b")


def _engine(phi4_setup, **kw):
    cfg, params = phi4_setup
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 48)
    kw.setdefault("block_size", 8)
    return ServingEngine(cfg, params=params, **kw)


def _assert_no_leaks(eng):
    cache = eng.sched.prefix_cache
    held = len(cache.held_blocks()) if cache is not None else 0
    assert eng.pool.used_blocks == held
    assert len(eng.sched.free_slots) == eng.slots
    assert not eng.sched.running and not eng.sched.swapped


def _assert_all_terminal(reqs):
    for r in reqs:
        assert r.terminal, f"rid {r.rid} stuck in {r.state}"
        assert r.t_done is not None


async def _collect(stream):
    """Drain one stream; returns (token tuples, done event, heartbeat count)."""
    toks, done, beats = [], None, 0
    async for ev in stream:
        if ev.kind == "token":
            toks.append(ev.token)
        elif ev.kind == "heartbeat":
            beats += 1
        else:
            done = ev
    return toks, done, beats


# ---------------------------------------------------------------- units

def test_token_bucket_refill_and_debt():
    b = TokenBucket(rate=10.0, burst=5.0, now=0.0)
    assert b.admit_ok(0.0) and b.retry_after_s(0.0) == 0.0
    b.debit(8.0, 0.0)                       # post-hoc billing → negative
    assert b.level == -3.0
    assert not b.admit_ok(0.0)
    # refill past one token: (1 - (-3)) / 10 = 0.4s
    assert b.retry_after_s(0.0) == pytest.approx(0.4)
    assert b.admit_ok(0.5)                  # -3 + 5 = 2 > 0
    b.debit(0.0, 10.0)                      # long idle caps at burst
    assert b.level == 5.0


def test_overloaded_typing():
    e = Overloaded("full", retry_after=1.5, tenant="t0")
    assert isinstance(e, RuntimeError)
    assert e.retry_after == 1.5 and e.tenant == "t0"
    s = ShuttingDown("bye")
    # one except-clause covers both rejection shapes
    assert isinstance(s, Overloaded) and s.retry_after is None


def test_victim_key_ranks_over_quota_first():
    class _Sched:
        victim_key = None
    class _Eng:
        on_token = None
        sched = _Sched()
        _done = []
    fd = FrontDoor.__new__(FrontDoor)      # key logic only, no event loop
    fd.tenant_rate = 1.0
    fd.buckets = {"hog": TokenBucket(1.0, 1.0, 0.0)}
    fd.buckets["hog"].debit(5.0, 0.0)      # over quota
    old_hog = Request(rid=0, prompt=np.zeros(4, np.int32), max_new=4,
                      arrival=0.0, tenant="hog")
    young = Request(rid=1, prompt=np.zeros(4, np.int32), max_new=4,
                    arrival=9.0, tenant="polite")
    # default policy would pick the youngest (rid 1); QoS key overrides
    assert max([old_hog, young], key=fd._victim_key) is old_hog
    fd.buckets["hog"].debit(-10.0, 0.0)    # back under quota
    assert max([old_hog, young], key=fd._victim_key) is young


# ---------------------------------------------------------------- parity

def test_stream_parity_with_sync_engine(phi4_setup):
    ref_reqs = make_requests(phi4_setup[0], mixed_spec(4), seed=9)
    eng0 = _engine(phi4_setup)
    eng0.run(ref_reqs)
    ref = token_streams(ref_reqs)

    eng = _engine(phi4_setup)
    reqs = make_requests(phi4_setup[0], mixed_spec(4), seed=9)

    async def main():
        fd = FrontDoor(eng, max_queue=16)
        await fd.start()
        outs = await asyncio.gather(*[_collect(fd.submit(r)) for r in reqs])
        await fd.aclose()
        return outs

    outs = asyncio.run(main())
    got = {r.rid: t for r, (t, _, _) in zip(reqs, outs)}
    assert got == ref
    for r, (toks, done, _) in zip(reqs, outs):
        assert done is not None and done.state == "done"
        assert done.n_tokens == len(toks) == r.n_generated
    # aclose restored the hooks: the engine is serviceable for direct use
    assert eng.on_token is None and eng.sched.victim_key is None
    _assert_all_terminal(reqs)
    _assert_no_leaks(eng)


def test_token_events_are_incremental(phi4_setup):
    eng = _engine(phi4_setup)
    req = make_requests(phi4_setup[0], mixed_spec(1), seed=9)[0]

    async def main():
        fd = FrontDoor(eng, max_queue=4)
        await fd.start()
        events = []
        async for ev in fd.submit(req):
            events.append(ev)
        await fd.aclose()
        return events

    events = asyncio.run(main())
    toks = [ev for ev in events if ev.kind == "token"]
    assert [ev.index for ev in toks] == list(range(len(toks)))
    # interpolated timestamps: monotone, and the done event is last
    ts = [ev.t for ev in toks]
    assert all(a <= b for a, b in zip(ts, ts[1:]))
    assert events[-1].kind == "done"
    assert sum(ev.kind == "done" for ev in events) == 1


# ---------------------------------------------------------------- backpressure

def test_queue_full_rejects_with_retry_after(phi4_setup):
    eng = _engine(phi4_setup, slots=2)
    reqs = make_requests(phi4_setup[0], mixed_spec(8), seed=9)

    async def main():
        fd = FrontDoor(eng, max_queue=2)
        await fd.start()
        streams, rejected = [], []
        for r in reqs:
            try:
                streams.append(fd.submit(r))
            except Overloaded as e:
                rejected.append(e)
        outs = await asyncio.gather(*[_collect(s) for s in streams])
        await fd.aclose()
        return outs, rejected, fd.summary()

    outs, rejected, summ = asyncio.run(main())
    # nothing was stepped during the submit burst, so everything past the
    # queue bound bounced (2 waiting max; admission to slots needs a step)
    assert rejected, "expected queue-full rejections"
    for e in rejected:
        assert isinstance(e, Overloaded) and not isinstance(e, ShuttingDown)
        assert e.retry_after is not None and e.retry_after >= 0.0
    assert summ["rejected_queue"] == len(rejected)
    assert summ["accepted"] == len(outs)
    for toks, done, _ in outs:
        assert done.state == "done" and len(toks) == done.n_tokens
    _assert_no_leaks(eng)


def test_degradation_denial_rejects_with_retry_after(phi4_setup):
    eng = _engine(phi4_setup, degrade=True)
    eng.degrade.level = 4                   # force admit_deny
    req = make_requests(phi4_setup[0], mixed_spec(1), seed=9)[0]

    async def main():
        fd = FrontDoor(eng, max_queue=8)
        await fd.start()
        try:
            with pytest.raises(Overloaded) as ei:
                fd.submit(req)
            return ei.value, fd.summary()
        finally:
            await fd.aclose()

    exc, summ = asyncio.run(main())
    assert exc.retry_after is not None and exc.retry_after >= 0.0
    assert summ["rejected_degrade"] == 1
    # the same relative hint surfaces in the operator summary
    snap = eng.degrade.snapshot(eng._now())
    assert snap["retry_after_s"] is not None and snap["retry_after_s"] >= 0.0
    assert eng._by_rid == {}                # rejected ⇒ no engine state


# ---------------------------------------------------------------- disconnects

def test_disconnect_mid_stream_cancels_and_frees(phi4_setup):
    eng = _engine(phi4_setup, slots=2)
    spec = mixed_spec(3, gen_buckets=(24,))
    reqs = make_requests(phi4_setup[0], spec, seed=9)

    async def main():
        fd = FrontDoor(eng, max_queue=8)
        await fd.start()

        async def flaky(r):
            stream = fd.submit(r)
            n = 0
            async for ev in stream:
                if ev.kind == "token":
                    n += 1
                    if n >= 3:
                        break
            # the disconnect: closing the generator fires its finally,
            # which cancels the request in the engine
            await stream.aclose()
            return n

        got = await asyncio.gather(_collect(fd.submit(reqs[0])),
                                   flaky(reqs[1]), flaky(reqs[2]))
        # let the driver route the cancellations before closing
        await asyncio.sleep(0)
        await fd.shutdown()
        return got, fd.summary()

    (full, n1, n2), summ = asyncio.run(main())
    assert full[1].state == "done"
    assert n1 == 3 and n2 == 3
    assert summ["disconnect_cancels"] == 2
    assert summ["live_streams"] == 0
    by_state = sorted(r.state.value for r in reqs)
    assert by_state == ["cancelled", "cancelled", "done"]
    for r in reqs[1:]:
        assert r.finish_reason == "disconnect"
    _assert_all_terminal(reqs)
    _assert_no_leaks(eng)


def test_slow_reader_loses_nothing(phi4_setup):
    eng = _engine(phi4_setup)
    reqs = make_requests(phi4_setup[0], mixed_spec(2, gen_buckets=(24,)),
                         seed=9)

    async def main():
        fd = FrontDoor(eng, max_queue=8)
        await fd.start()

        async def slow(r):
            toks = []
            async for ev in fd.submit(r):
                await asyncio.sleep(0.002)    # reader slower than the engine
                if ev.kind == "token":
                    toks.append(ev.token)
            return toks

        fast = _collect(fd.submit(reqs[0]))
        outs = await asyncio.gather(fast, slow(reqs[1]))
        await fd.aclose()
        return outs

    (fast_toks, done, _), slow_toks = asyncio.run(main())
    assert done.state == "done"
    # backpressure never drops events: the slow reader still gets them all
    assert len(slow_toks) == reqs[1].n_generated == 24
    assert len(fast_toks) == reqs[0].n_generated
    _assert_all_terminal(reqs)
    _assert_no_leaks(eng)


def test_deadline_expiry_streams_timeout(phi4_setup):
    eng = _engine(phi4_setup, slots=1)
    reqs = make_requests(phi4_setup[0], mixed_spec(2, gen_buckets=(24,)),
                         seed=9)

    async def main():
        fd = FrontDoor(eng, max_queue=8)
        await fd.start()
        s0 = fd.submit(reqs[0])
        reqs[1].deadline = eng._now()         # expires at the next step top
        s1 = fd.submit(reqs[1])
        outs = await asyncio.gather(_collect(s0), _collect(s1))
        await fd.aclose()
        return outs

    (t0, d0, _), (t1, d1, _) = asyncio.run(main())
    assert d0.state == "done" and len(t0) == 24
    assert d1.state == "timeout" and d1.finish_reason == "deadline"
    _assert_all_terminal(reqs)
    _assert_no_leaks(eng)


# ---------------------------------------------------------------- shutdown

def test_shutdown_mid_burst_flushes_and_rejects_late(phi4_setup):
    eng = _engine(phi4_setup, slots=2)
    reqs = make_requests(phi4_setup[0], mixed_spec(6, gen_buckets=(24,)),
                         seed=9)

    async def main():
        fd = FrontDoor(eng, max_queue=8)
        await fd.start()
        streams = [fd.submit(r) for r in reqs[:5]]
        tasks = [asyncio.ensure_future(_collect(s)) for s in streams]
        # give the engine a few steps so some requests are truly in flight
        for _ in range(30):
            await asyncio.sleep(0)
        shut = asyncio.ensure_future(fd.shutdown())
        await asyncio.sleep(0)
        # late submission during the drain: typed rejection, never a hang
        with pytest.raises(ShuttingDown):
            fd.submit(reqs[5])
        outs = await asyncio.gather(*tasks)
        await shut
        return outs, fd.summary()

    outs, summ = asyncio.run(main())
    assert summ["rejected_draining"] == 1
    states = sorted(d.state for _, d, _ in outs)
    # every admitted stream flushed exactly one terminal event; in-flight
    # requests ran to completion, never-admitted ones cancelled as "drain"
    assert all(s in ("done", "cancelled") for s in states)
    assert "done" in states
    for r, (toks, done, _) in zip(reqs[:5], outs):
        assert done.n_tokens == len(toks) == r.n_generated
        if done.state == "cancelled":
            assert r.finish_reason == "drain" and r.t_admit is None
    _assert_all_terminal(reqs[:5])
    assert not reqs[5].terminal and reqs[5].rid not in eng._by_rid
    _assert_no_leaks(eng)


# ---------------------------------------------------------------- tenants

def test_tenant_quota_storm(phi4_setup):
    eng = _engine(phi4_setup)
    spec = mixed_spec(8, gen_buckets=(8,), n_tenants=2)
    reqs = make_requests(phi4_setup[0], spec, seed=9)
    hog = [r for r in reqs if r.tenant == "t0"]
    polite = [r for r in reqs if r.tenant == "t1"]

    async def main():
        # burst covers ~2 requests of emitted tokens; refill is negligible
        # on this run's wall-clock timescale, so the storm outcome is exact
        fd = FrontDoor(eng, max_queue=16, tenant_rate=1e-3, tenant_burst=12.0)
        await fd.start()
        admitted, rejected = [], []
        for r in hog:
            try:
                admitted.append(asyncio.ensure_future(_collect(fd.submit(r))))
                await asyncio.gather(admitted[-1])   # serialize: drain quota
            except Overloaded as e:
                rejected.append(e)
        polite_outs = await asyncio.gather(
            *[_collect(fd.submit(r)) for r in polite])
        outs = await asyncio.gather(*admitted)
        await fd.aclose()
        return outs, rejected, polite_outs, fd.summary()

    outs, rejected, polite_outs, summ = asyncio.run(main())
    # the hog burns its bucket and starts bouncing; rejections carry the
    # refill-sized hint and the tenant id
    assert rejected and summ["rejected_quota"] == len(rejected)
    for e in rejected:
        assert e.tenant == "t0"
        assert e.retry_after is not None and e.retry_after > 0.0
    # the polite tenant is untouched by the hog's storm
    assert all(d.state == "done" for _, d, _ in polite_outs)
    assert summ["tenant_buckets"]["t0"] <= 0.0
    _assert_no_leaks(eng)


def test_per_tenant_metrics_and_bills(phi4_setup):
    eng = _engine(phi4_setup)
    spec = mixed_spec(4, n_tenants=2)
    reqs = make_requests(phi4_setup[0], spec, seed=9)

    async def main():
        fd = FrontDoor(eng, max_queue=16)
        await fd.start()
        await asyncio.gather(*[_collect(fd.submit(r)) for r in reqs])
        await fd.aclose()

    asyncio.run(main())
    s = eng.summary()
    # per-tenant aggregate: terminal counts, token totals, latency, energy
    assert set(s["tenants"]) == {"t0", "t1"}
    for t, agg in s["tenants"].items():
        assert agg["requests"] == 2
        assert agg["terminal"]["done"] == 2
        assert agg["generated_tokens"] > 0
        assert agg["energy_mj"] > 0.0
        assert agg["ttft_s"]["p50"] >= 0.0
    assert sum(a["generated_tokens"] for a in s["tenants"].values()) \
        == s["engine_stats"]["generated_tokens"]
    # per-request records carry the tenant id
    assert {r["tenant"] for r in s["requests"]} == {"t0", "t1"}
    # windowed per-tenant TTFT/TPOT histograms exist in the registry
    hists = s["metrics"]["histograms"]
    assert "ttft_s/t0" in hists and "ttft_s/t1" in hists


def test_untenanted_summary_keeps_schema(phi4_setup):
    eng = _engine(phi4_setup)
    reqs = make_requests(phi4_setup[0], mixed_spec(2), seed=9)
    eng.run(reqs)
    s = eng.summary()
    assert "tenants" not in s
    assert all(r["tenant"] is None for r in s["requests"])


# ---------------------------------------------------------------- heartbeats

def test_heartbeats_on_idle_streams(phi4_setup):
    eng = _engine(phi4_setup, slots=1)
    reqs = make_requests(phi4_setup[0], mixed_spec(2, gen_buckets=(24,)),
                         seed=9)

    async def main():
        fd = FrontDoor(eng, max_queue=8, heartbeat_s=1e-6)
        await fd.start()
        s0 = fd.submit(reqs[0])

        first_kind = {}

        async def watch(r, stream):
            beats = 0
            async for ev in stream:
                first_kind.setdefault(r.rid, ev.kind)
                if ev.kind == "heartbeat":
                    beats += 1
                    assert ev.state in ("queued", "running", "swapped")
            return beats

        s1 = fd.submit(reqs[1])               # queued behind the only slot
        b0, b1 = await asyncio.gather(watch(reqs[0], s0), watch(reqs[1], s1))
        await fd.aclose()
        return b0, b1, first_kind, fd.summary()

    b0, b1, first_kind, summ = asyncio.run(main())
    # the queued stream heartbeats while it waits for its slot
    assert b1 > 0 and summ["heartbeats"] == b0 + b1
    assert first_kind[reqs[1].rid] == "heartbeat"
    _assert_all_terminal(reqs)
    _assert_no_leaks(eng)


# ---------------------------------------------------------------- engine failure

@pytest.mark.parametrize("fail_at", [0, 2])
def test_engine_step_failure_ends_every_stream(phi4_setup, fail_at):
    """A step that raises (a device or compile error) must end every open
    stream with a terminal ``failed`` event and surface the exception to the
    driver's awaiter and to ``shutdown()`` — nothing may hang."""
    eng = _engine(phi4_setup)
    reqs = make_requests(phi4_setup[0], mixed_spec(4), seed=9)
    real_step, calls = eng.step, [0]

    def step():
        calls[0] += 1
        if calls[0] > fail_at:
            raise RuntimeError("injected dispatch failure")
        return real_step()

    eng.step = step

    async def main():
        fd = FrontDoor(eng, max_queue=16)
        await fd.start()
        streams = [fd.submit(r) for r in reqs]
        outs = await asyncio.wait_for(
            asyncio.gather(*[_collect(s) for s in streams]), timeout=60)
        with pytest.raises(RuntimeError, match="injected"):
            await fd._driver
        with pytest.raises(RuntimeError, match="engine failed"):
            fd.submit(make_requests(phi4_setup[0], mixed_spec(1), seed=3)[0])
        with pytest.raises(RuntimeError, match="injected"):
            await asyncio.wait_for(fd.shutdown(), timeout=60)
        return outs

    outs = asyncio.run(main())
    assert len(outs) == len(reqs)
    for r, (toks, done, _) in zip(reqs, outs):
        assert done is not None, f"rid {r.rid} has no terminal event"
        if done.state != "done":
            assert done.state == "failed"
            assert done.finish_reason == "engine_error"
        assert done.n_tokens == len(toks)
    assert any(done.state == "failed" for _, done, _ in outs)
    # shutdown restored the hooks even though it raised
    assert eng.on_token is None and eng.sched.victim_key is None
