"""Host phases of the serving loop: spans on the profiler's clock, the
always-on phase counters, garbage-collection and delivery counters, and the
benchmark's three readers of them.

The phases (``plan``, ``pack``, ``tables``, a dispatch's kind, ``sync``,
``wear``, ``emit``, ``deliver``, ``idle``, ``gc``) tile the front door's
driver loop: no two overlap, so a profiler idle gap is labelled by exactly
the phase the host was in.
"""
import asyncio
import gc
import glob
import importlib.util
import os
import time
from types import SimpleNamespace

import jax
import pytest

from serving_harness import materialize, mixed_spec
from repro.serving import (NULL_TRACER, EngineStats, FrontDoor, NullTracer,
                           ServingEngine, Tracer, make_requests)

STEP_FIELDS = ("host_plan_s", "host_pack_s", "host_tables_s",
               "dispatch_launch_s", "dispatch_sync_s", "host_wear_s",
               "host_emit_s")
READERS = os.path.join(os.path.dirname(__file__), "..", "bench",
                       "layer_metrics")


class _Tick:
    """Deterministic clock: each read advances one millisecond."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


@pytest.fixture(scope="module")
def phi4_setup():
    return materialize("phi4-mini-3.8b")


def _engine(phi4_setup, **kw):
    cfg, params = phi4_setup
    return ServingEngine(cfg, slots=3, max_len=48, block_size=8,
                         params=params, **kw)


def _host_spans(events):
    return sorted((ev.ts, ev.ts + ev.dur, ev.name) for ev in events
                  if ev.ph == "X" and ev.cat == "host")


def _assert_disjoint(spans):
    for (s0, e0, n0), (s1, e1, n1) in zip(spans, spans[1:]):
        assert e0 <= s1, f"{n0} [{s0}, {e0}) overlaps {n1} [{s1}, {e1})"


# ---------------------------------------------------------------- tracer

def _hooks():
    """``len(gc.callbacks)`` once unreachable tracers have taken theirs."""
    gc.collect()
    return len(gc.callbacks)


def test_null_phase_is_one_shared_noop():
    n0 = _hooks()
    tr = NullTracer()
    tr.attach(time.perf_counter, stats=EngineStats(), annotate=True)
    assert len(gc.callbacks) == n0                 # no hook with tracing off
    ph = tr.phase("plan")
    assert ph is NULL_TRACER.phase("emit")
    with ph:
        pass
    assert NULL_TRACER.events() == ()


def test_nested_phase_and_gc_split_the_open_phase():
    tr = Tracer()
    stats = EngineStats()
    tr.attach(_Tick(), stats=stats)
    try:
        with tr.phase("plan"):
            with tr.phase("prefill"):
                pass
            gc.collect()
    finally:
        tr.detach()
    spans = _host_spans(tr.events())
    assert [n for _, _, n in spans] == ["serving/plan", "serving/prefill",
                                        "serving/plan", "serving/gc",
                                        "serving/plan"]
    _assert_disjoint(spans)
    # each segment starts where the previous one ended: the phases tile
    assert all(e0 == s1 for (_, e0, _), (s1, _, _) in zip(spans, spans[1:]))
    assert stats.gc_collections >= 1 and stats.gc_pause_s > 0
    assert all(ev.args is None for ev in tr.events())


def test_gc_hook_leaves_with_detach_or_with_the_tracer():
    n0 = _hooks()
    tr = Tracer()
    tr.attach(time.perf_counter, stats=EngineStats())
    tr.attach(time.perf_counter, stats=EngineStats())   # re-attach: one hook
    assert len(gc.callbacks) == n0 + 1
    tr.detach()
    tr.detach()
    assert len(gc.callbacks) == n0
    dropped = Tracer()
    dropped.attach(time.perf_counter)
    assert len(gc.callbacks) == n0 + 1
    del dropped
    gc.collect()
    assert len(gc.callbacks) == n0


# ---------------------------------------------------------------- engine

def test_phase_counters_fit_inside_the_step_wall(phi4_setup):
    """Σ step phases ≤ the wall of the steps (GC pauses counted apart), and
    the phases account for nearly all of it; nothing outside a front door
    counts as delivery or idle."""
    tracer = Tracer()
    eng = _engine(phi4_setup, tracer=tracer, horizon=4)
    try:
        for req in make_requests(eng.cfg, mixed_spec(), seed=9):
            eng.submit(req)
        wall = 0.0
        steps = 0
        while eng.sched.has_work:
            t0 = eng._now()
            eng.step()
            wall += eng._now() - t0
            steps += 1
    finally:
        tracer.detach()
    st = eng.stats
    phases = sum(getattr(st, f) for f in STEP_FIELDS)
    assert all(getattr(st, f) > 0 for f in STEP_FIELDS)
    assert phases + st.gc_pause_s <= wall
    assert phases >= 0.9 * (wall - st.gc_pause_s)
    assert st.deliver_s == st.idle_wait_s == 0.0
    assert st.steps == steps
    _assert_disjoint(_host_spans(tracer.events()))


def _profiled_front_door(phi4_setup, trace_dir):
    n0 = _hooks()
    tracer = Tracer(capacity=1 << 16)
    eng = _engine(phi4_setup, tracer=tracer, xla_annotations=True,
                  clock=time.perf_counter)
    record = eng._record_writes
    forced = []

    def record_then_collect(*a, **kw):
        record(*a, **kw)
        if not forced and eng.stats.steps == 3:
            forced.append(eng.stats.steps)
            gc.collect()
    eng._record_writes = record_then_collect

    async def main():
        fd = FrontDoor(eng, max_queue=16)
        await fd.start()
        consumed = []

        async def consume(stream):
            async for ev in stream:
                if ev.kind == "token":
                    consumed.append(ev)
        streams = [fd.submit(r)
                   for r in make_requests(eng.cfg, mixed_spec(), seed=9)]
        await asyncio.gather(*(consume(s) for s in streams))
        hooked = len(gc.callbacks)
        await fd.aclose()
        return consumed, hooked

    with jax.profiler.trace(str(trace_dir)):
        consumed, hooked = asyncio.run(main())
    assert hooked == n0 + 1 and len(gc.callbacks) == n0  # aclose unhooks
    assert forced
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in line.events
                          if ev.name.startswith("serving/")]
    return eng, tracer, consumed, sorted(spans)


def test_front_door_phases_tile_the_profiled_loop(phi4_setup, tmp_path):
    """Under the profiler, the host ``serving/*`` annotations are pairwise
    disjoint and cover ≥95% of the driver's wall time from its first step to
    its last; a collection forced inside a step is a ``serving/gc`` span
    between two segments of the phase it interrupted; every consumed token
    counts once toward the delivery lag."""
    eng, tracer, consumed, spans = _profiled_front_door(phi4_setup, tmp_path)
    _assert_disjoint(spans)
    _assert_disjoint(_host_spans(tracer.events()))
    names = {n for _, _, n in spans}
    assert {"serving/plan", "serving/pack", "serving/tables", "serving/mixed",
            "serving/sync", "serving/wear", "serving/emit",
            "serving/deliver", "serving/gc"} <= names
    plans = [s for s, _, n in spans if n == "serving/plan"]
    lo, hi = plans[0], plans[-1]
    covered = sum(min(e, hi) - max(s, lo) for s, e, _ in spans
                  if e > lo and s < hi)
    assert covered >= 0.95 * (hi - lo)
    at = [i for i, (_, _, n) in enumerate(spans) if n == "serving/gc"]
    assert any(spans[i - 1][2] == spans[i + 1][2] == "serving/wear"
               and spans[i - 1][1] <= spans[i][0]
               and spans[i][1] <= spans[i + 1][0] for i in at)
    st = eng.stats
    assert st.delivered_tokens == len(consumed) > 0
    assert st.deliver_lag_s >= 0.0
    assert st.gc_collections >= 1 and st.deliver_s > 0


# ---------------------------------------------------------------- readers

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), os.path.join(READERS,
                                                         name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _record(**stats):
    return SimpleNamespace(stats=stats, window=SimpleNamespace(seconds=50.0),
                           spans=[], trace=None)


@pytest.mark.parametrize("name, stats, want", [
    ("host_step_ms.docs",
     dict(steps=400, host_plan_s=0.4, host_pack_s=0.2, host_tables_s=0.1,
          host_wear_s=0.3, host_emit_s=1.0, dispatch_sync_s=9.0),
     1e3 * 2.0 / 400),
    ("deliver_lag_ms.poisson",
     dict(deliver_lag_s=0.25, delivered_tokens=1000), 0.25),
    ("gc_pause_pct.poisson", dict(gc_pause_s=0.5, gc_collections=7), 1.0),
])
def test_reader_on_a_synthetic_record(name, stats, want):
    assert _reader(name)(_record(**stats)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["host_step_ms.docs",
                                  "deliver_lag_ms.poisson",
                                  "gc_pause_pct.poisson"])
def test_reader_is_silent_without_its_counters(name):
    """The parent's program has none of these counters: no value, no error."""
    assert _reader(name)(_record(steps=400, decode_time=3.0)) is None
