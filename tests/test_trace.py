"""Tests for repro.serving.trace: the ring-buffered tracer, Chrome-trace
export/validation, the windowed metrics registry — and the engine
integration that threads them through the serving stack.

The engine tests pin the observability contract end to end: every compiled-
step launch emits a dispatch span whose ODIN energy bill sums (with prefill
chunks and spec overhead) exactly to the run's ``odin_total``; request
lifecycle events stay ordered and flow-linked across swap preemption; and
the trace-off path calls zero recorder methods (the <2%-overhead guarantee
is structural, not statistical).
"""
import dataclasses
import json

import numpy as np
import pytest

from serving_harness import materialize, mixed_spec, run_workload

from repro.serving import (NULL_TRACER, EngineStats, LogHistogram,
                           MetricsRegistry, NullTracer, ReliabilityConfig,
                           Request, ServingEngine, Tracer, chrome_trace,
                           make_requests, summarize, validate_chrome_trace)


# ---------------------------------------------------------------------------
# ring buffer
# ---------------------------------------------------------------------------

def test_tracer_ring_drops_oldest_and_counts():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}", "test", "scheduler", ts=float(i))
    assert len(tr) == 4
    assert tr.dropped_events == 6
    assert [ev.name for ev in tr.events()] == ["e6", "e7", "e8", "e9"]
    # drops are recorded in the export so a truncated trace is detectable
    assert tr.to_chrome()["otherData"]["dropped_events"] == 6


def test_tracer_capacity_validation():
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_tracer_clock_default_timestamps():
    t = [0.0]
    tr = Tracer()
    tr.set_clock(lambda: t[0])
    t[0] = 2.5
    tr.instant("a", "test", "scheduler")
    assert tr.events()[0].ts == 2.5
    tr.instant("b", "test", "scheduler", ts=1.0)      # explicit ts wins
    assert tr.events()[1].ts == 1.0


# ---------------------------------------------------------------------------
# chrome export + schema validation
# ---------------------------------------------------------------------------

def _sample_tracer():
    tr = Tracer()
    tr.flow_event("s", "request", "scheduler", 7, ts=0.0)
    tr.instant("queued", "lifecycle", "scheduler", ts=0.0,
               args={"rid": 7}, flow=7)
    tr.span("prefill-chunk", "dispatch", "slot 1", 0.1, 0.05,
            args={"rows": 16, "odin_energy_mj": 1.5}, flow=7)
    tr.flow_event("t", "request", "slot 1", 7, ts=0.1)
    tr.counter("kv blocks", "pool", {"used": 3, "free": 5}, ts=0.2)
    tr.span("decode", "dispatch", "dispatch", 0.2, 0.01,
            args={"kind": "decode"})
    tr.flow_event("f", "request", "slot 1", 7, ts=0.3)
    return tr


def test_chrome_trace_schema_valid_and_strict_json(tmp_path):
    tr = _sample_tracer()
    obj = tr.export(str(tmp_path / "t.json"))
    assert validate_chrome_trace(obj) == []
    # the file on disk round-trips strict JSON and matches the object
    loaded = json.loads((tmp_path / "t.json").read_text())
    assert loaded == json.loads(json.dumps(obj, allow_nan=False))
    evs = obj["traceEvents"]
    # metadata names every track; slot lanes sort before scheduler/pool
    names = [e["args"]["name"] for e in evs if e["name"] == "thread_name"]
    assert names[0] == "slot 1"
    assert set(names) == {"slot 1", "scheduler", "pool", "dispatch"}
    # seconds → microseconds
    span = next(e for e in evs if e["ph"] == "X" and e["name"] == "decode")
    assert span["ts"] == pytest.approx(0.2e6) and span["dur"] == pytest.approx(0.01e6)
    # flow anchors carry the id; the finish binds to the enclosing slice
    fin = next(e for e in evs if e["ph"] == "f")
    assert fin["id"] == 7 and fin["bp"] == "e"
    # non-flow events with a flow expose it as args.flow_id
    pre = next(e for e in evs if e["name"] == "prefill-chunk")
    assert pre["args"]["flow_id"] == 7


def test_validate_chrome_trace_rejects_corruption():
    obj = _sample_tracer().to_chrome()
    assert validate_chrome_trace(obj) == []

    bad = json.loads(json.dumps(obj))
    next(e for e in bad["traceEvents"] if e["ph"] == "i")["ts"] = float("nan")
    assert any("bad ts" in e for e in validate_chrome_trace(bad))

    bad = json.loads(json.dumps(obj))
    del next(e for e in bad["traceEvents"] if e["ph"] == "X")["dur"]
    assert any("bad dur" in e for e in validate_chrome_trace(bad))

    bad = json.loads(json.dumps(obj))
    next(e for e in bad["traceEvents"] if e["ph"] == "C")["ph"] = "Z"
    assert any("unknown phase" in e for e in validate_chrome_trace(bad))

    bad = json.loads(json.dumps(obj))
    del next(e for e in bad["traceEvents"] if e["ph"] == "s")["id"]
    assert any("missing id" in e for e in validate_chrome_trace(bad))

    assert validate_chrome_trace([1, 2]) != []
    assert validate_chrome_trace({"traceEvents": "nope"}) != []


def test_validate_flow_ordering_relaxed_under_drops():
    """An orphan flow step is an error in a complete trace but expected when
    the ring dropped its 's' anchor."""
    tr = Tracer()
    tr.flow_event("t", "request", "slot 0", 3, ts=0.0)   # no "s" recorded
    obj = tr.to_chrome()
    assert any("before its 's'" in e for e in validate_chrome_trace(obj))
    obj["otherData"]["dropped_events"] = 5
    assert validate_chrome_trace(obj) == []


def test_flow_phase_validation():
    with pytest.raises(ValueError):
        Tracer().flow_event("x", "request", "slot 0", 1)


# ---------------------------------------------------------------------------
# satellite 1: empty-run summaries are strict JSON
# ---------------------------------------------------------------------------

def test_zero_request_summary_round_trips_strict_json():
    """percentiles([]) must yield None (JSON null), never float('nan') —
    a bare NaN token makes the summary unparseable by any strict reader."""
    summary = summarize([], EngineStats())
    text = json.dumps(summary, allow_nan=False)       # would raise on NaN
    back = json.loads(text)
    assert back["ttft_s"] == {"p50": None, "p90": None, "p99": None}
    assert back["tpot_s"]["p99"] is None
    assert back["generated_tokens"] == 0


# ---------------------------------------------------------------------------
# log histogram + metrics registry
# ---------------------------------------------------------------------------

def test_log_histogram_percentiles_within_bucket_ratio():
    h = LogHistogram(lo=1e-6, hi=1e4, bins_per_decade=6)
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-3.0, sigma=1.0, size=2000)
    for x in xs:
        h.observe(float(x))
    ratio = 10 ** (1 / 6)                             # one bucket's width
    for q in (50, 90, 99):
        exact = float(np.percentile(xs, q))
        est = h.percentile(q)
        assert exact / ratio <= est <= exact * ratio
    s = h.summary()
    assert s["count"] == 2000
    assert s["mean"] == pytest.approx(float(np.mean(xs)))


def test_log_histogram_empty_and_out_of_range():
    h = LogHistogram(lo=1e-3, hi=1e3, bins_per_decade=3)
    assert h.percentile(50) is None
    assert h.summary()["mean"] is None
    h.observe(1e-9)                                   # underflow bucket
    h.observe(1e9)                                    # overflow bucket
    assert h.total == 2
    assert h.percentile(25) == 0.0                    # underflow midpoint
    assert h.percentile(99) == 1e3                    # clamped at hi


def test_log_histogram_delta_summary_windows():
    h = LogHistogram()
    h.observe(0.1)
    marks = h.marks()
    h.observe(0.2)
    h.observe(0.4)
    d = h.delta_summary(marks)
    assert d["count"] == 2
    assert d["mean"] == pytest.approx(0.3)
    assert h.summary()["count"] == 3                  # cumulative unchanged


def test_metrics_registry_rolls_aligned_windows():
    reg = MetricsRegistry(window_s=1.0)
    reg.maybe_roll(0.2, {"tok": 0})                   # opens; boundary at 1.0
    reg.observe("lat_s", 0.01)
    reg.maybe_roll(0.9, {"tok": 3})                   # boundary not reached
    assert reg.windows == []
    reg.observe("lat_s", 0.02)
    reg.maybe_roll(1.1, {"tok": 5})                   # closes [0, 1)
    assert len(reg.windows) == 1
    w = reg.windows[0]
    assert (w["t0"], w["t1"]) == (0.0, 1.0)
    assert w["counters"] == {"tok": 5}
    assert w["histograms"]["lat_s"]["count"] == 2
    # idle gap: boundaries pass with no movement → windows elided
    reg.maybe_roll(4.2, {"tok": 5})
    assert len(reg.windows) == 1
    reg.observe("lat_s", 0.03)
    reg.flush(4.6, {"tok": 9})                        # partial window close
    assert len(reg.windows) == 2
    w = reg.windows[1]
    assert w["t0"] == 4.0 and w["t1"] == pytest.approx(4.6)
    assert w["counters"] == {"tok": 4}
    summary = reg.summary()
    assert summary["histograms"]["lat_s"]["count"] == 3
    json.dumps(summary, allow_nan=False)


def test_metrics_registry_gauges_and_validation():
    with pytest.raises(ValueError):
        MetricsRegistry(window_s=0)
    reg = MetricsRegistry()
    reg.set_gauge("free_blocks", 7)
    assert reg.summary()["gauges"] == {"free_blocks": 7.0}


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

class _Clock:
    """Deterministic strictly-increasing engine clock."""

    def __init__(self, dt=1e-3):
        self.t, self.dt = 0.0, dt

    def __call__(self):
        self.t += self.dt
        return self.t


def _traced_run(**kw):
    cfg, params = materialize("phi4-mini-3.8b")
    tracer = Tracer()
    eng = ServingEngine(cfg, slots=3, max_len=48, block_size=8, params=params,
                        clock=_Clock(), tracer=tracer, **kw)
    reqs = make_requests(cfg, mixed_spec(), seed=9)
    summary = eng.run(reqs)
    tracer.detach()                  # no GC hook outlives the test
    return tracer, summary, eng


def test_engine_trace_spans_and_energy_attribution():
    """Every dispatch span carries its ODIN bill; the bills sum to the run's
    odin_total (1%-gate satisfied by construction), and the trace validates."""
    tracer, summary, _ = _traced_run(horizon=4)
    obj = tracer.to_chrome()
    assert validate_chrome_trace(obj) == []
    kinds = {ev.name for ev in tracer.events() if ev.ph == "X"}
    # phi4 is fully paged, so mixed dispatch is auto-on: prefill rides in
    # "mixed" tiles; pure-decode ticks still use decode/horizon dispatches
    assert {"mixed", "horizon"} <= kinds
    span_energy = sum((ev.args or {}).get("odin_energy_mj", 0.0)
                     for ev in tracer.events() if ev.ph == "X")
    assert span_energy == pytest.approx(summary["odin_total"]["energy_mj"],
                                        rel=1e-9)
    # dispatch spans carry the contract args
    for ev in tracer.events():
        if ev.ph == "X" and ev.name in ("decode", "horizon", "spec-horizon"):
            assert {"kind", "h", "spec_k", "slots_active", "tokens", "rows",
                    "host_syncs", "odin_energy_mj"} <= set(ev.args)
        if ev.ph == "X" and ev.name == "mixed":
            assert {"kind", "q_tile", "slots_active", "tokens", "rows",
                    "decode_rows", "prefill_rows", "host_syncs",
                    "odin_energy_mj"} <= set(ev.args)

    # the legacy separate-launch taxonomy survives under --no-mixed, with
    # the same exact span-energy attribution
    tracer, summary, _ = _traced_run(horizon=4, mixed=False)
    kinds = {ev.name for ev in tracer.events() if ev.ph == "X"}
    assert {"prefill-chunk", "horizon"} <= kinds and "mixed" not in kinds
    span_energy = sum((ev.args or {}).get("odin_energy_mj", 0.0)
                     for ev in tracer.events() if ev.ph == "X")
    assert span_energy == pytest.approx(summary["odin_total"]["energy_mj"],
                                        rel=1e-9)


def test_engine_trace_lifecycle_ordering_and_flow_survives_preemption():
    """queued → admit → … → complete stays clock-ordered per request, and the
    flow chain (s at queued, t at admit/swap/resume, f at complete) follows
    the request across a swap preemption."""
    tracer, summary, _ = _traced_run(n_blocks=8, swap_blocks=32)
    assert summary["preemptions"]["swap"] > 0
    by_rid = {}
    for ev in tracer.events():
        if ev.flow is not None:
            by_rid.setdefault(ev.flow, []).append(ev)
    assert by_rid
    preempted = {ev.flow for ev in tracer.events()
                 if ev.name in ("preempt-swap", "swap-copy")}
    assert preempted
    for rid, evs in by_rid.items():
        names = [ev.name for ev in evs]
        assert names[0] == "request" and evs[0].ph == "s"   # flow start
        assert "queued" in names and "admit" in names and "complete" in names
        assert names.index("queued") < names.index("admit") < names.index("complete")
        assert [ev.ph for ev in evs].count("s") == 1
        assert evs[-1].ph == "f"                            # flow finish last
        ts = [ev.ts for ev in evs]
        assert ts == sorted(ts)                             # clock-ordered
    for rid in preempted:
        names = [ev.name for ev in by_rid[rid]]
        if "swap-downgrade" in names:                       # swap tier full —
            continue                                        # requeued instead
        assert "resume" in names                            # swapped back in
        assert names.index("preempt-swap") < names.index("resume")
        assert names.index("resume") < names.index("complete")


def test_engine_trace_scheduler_and_pool_decisions():
    tracer, summary, _ = _traced_run(horizon=4, n_blocks=8, swap_blocks=32)
    names = {ev.name for ev in tracer.events()}
    assert {"admit", "grant_horizon", "alloc", "release"} <= names
    grants = [ev for ev in tracer.events() if ev.name == "grant_horizon"]
    assert all({"max_h", "granted", "available_blocks"} <= set(g.args)
               for g in grants)
    admits = [ev for ev in tracer.events() if ev.name == "admit"]
    assert all({"rid", "slot", "marginal_blocks"} <= set(a.args)
               for a in admits)
    counters = [ev for ev in tracer.events() if ev.ph == "C"]
    assert counters and all("free" in ev.args for ev in counters)


class _SpyTracer(NullTracer):
    """enabled=False recorder that counts any emit that still happens, and
    every distinct object its phases hand out."""

    def __init__(self):
        self.calls = 0
        self.phases = set()

    def phase(self, name):
        ph = NullTracer.phase(self, name)
        self.phases.add(id(ph))
        return ph

    def span(self, *a, **kw):
        self.calls += 1

    def instant(self, *a, **kw):
        self.calls += 1

    def counter(self, *a, **kw):
        self.calls += 1

    def flow_event(self, *a, **kw):
        self.calls += 1


def test_engine_trace_off_emits_nothing(monkeypatch):
    """The no-op path must not merely record nothing — it must never be
    called: every emit site guards on tracer.enabled, so trace-off skips
    even the argument-dict construction.  Host phases get the one shared
    no-op object, build no profiler annotation (even with
    ``xla_annotations``) and install no GC hook."""
    import gc

    import jax
    annotations = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: annotations.append(name))
    gc.collect()                     # unreachable tracers take their hooks
    n_hooks = len(gc.callbacks)
    cfg, params = materialize("phi4-mini-3.8b")
    eng = ServingEngine(cfg, slots=3, max_len=48, block_size=8, params=params)
    assert eng.tracer is NULL_TRACER                  # off by default
    spy = _SpyTracer()
    eng = ServingEngine(cfg, slots=3, max_len=48, block_size=8, params=params,
                        n_blocks=8, swap_blocks=32, horizon=4, tracer=spy,
                        xla_annotations=True)
    eng.run(make_requests(cfg, mixed_spec(), seed=9))
    assert spy.calls == 0
    assert spy.phases == {id(NULL_TRACER.phase("plan"))}
    assert annotations == []
    assert len(gc.callbacks) == n_hooks
    assert eng.stats.host_plan_s > 0 and eng.stats.gc_collections == 0


def test_engine_stats_fields_all_reported_in_summary():
    """CI consistency check: every EngineStats counter must appear in
    summarize()'s engine_stats mirror — a new dataclass field can never
    silently go unreported."""
    _, summary, _ = _traced_run()
    fields = {f.name for f in dataclasses.fields(EngineStats)}
    assert set(summary["engine_stats"]) == fields
    # the PCRAM reliability counters ride in EngineStats and must therefore
    # be in the mirror too — plus their curated summary section
    assert {"pool_writes", "retired_blocks", "scrub_copies", "scrub_rows",
            "wear_p99", "wear_max"} <= fields
    assert set(summary["reliability"]) == {
        "pool_writes", "retired_blocks", "scrub_copies", "scrub_rows",
        "wear_p99", "wear_max"}
    json.dumps(summary, allow_nan=False)


def test_reliability_scrub_phase_energy_attribution_exact():
    """With the drift scrubber on, scrub rows join ``odin_phases`` as their
    own phase, phase rows/energy still sum exactly to ``odin_total``, and
    every scrub span carries its own ODIN bill so trace-span energies stay
    an exact partition of the run's total."""
    tracer, summary, _ = _traced_run(
        horizon=4,
        reliability=ReliabilityConfig(scrub_rate=2, drift_deadline_s=0.02))
    rel = summary["reliability"]
    assert rel["pool_writes"] > 0 and rel["scrub_rows"] > 0
    phases = summary["odin_phases"]
    assert phases["scrub"]["rows"] == rel["scrub_rows"]
    assert sum(p["rows"] for p in phases.values()) == summary["odin_total"]["tokens"]
    assert sum(p["energy_mj"] for p in phases.values()) == pytest.approx(
        summary["odin_total"]["energy_mj"])
    span_energy = sum((ev.args or {}).get("odin_energy_mj", 0.0)
                      for ev in tracer.events() if ev.ph == "X")
    assert span_energy == pytest.approx(summary["odin_total"]["energy_mj"],
                                        rel=1e-9)
    scrubs = [ev for ev in tracer.events()
              if ev.ph == "X" and ev.name == "scrub"]
    assert scrubs
    assert all({"kind", "blocks", "rows", "odin_energy_mj"} <= set(ev.args)
               for ev in scrubs)
    assert {ev.args["kind"] for ev in scrubs} <= {"drift-refresh",
                                                  "retire-drain"}


def test_engine_metrics_windows_and_histograms():
    _, summary, eng = _traced_run(horizon=4)
    m = summary["metrics"]
    assert m["window_s"] == 1.0
    hists = m["histograms"]
    # mixed dispatch is auto-on for phi4: prefill rows ride in mixed tiles
    # (dispatch_mixed_s); pure-decode ticks still observe dispatch_decode_s
    assert {"ttft_s", "dispatch_mixed_s", "dispatch_decode_s"} <= set(hists)
    assert hists["ttft_s"]["count"] == len(summary["requests"])
    total_disp = sum(w["counters"].get("dispatches", 0) for w in m["windows"])
    assert total_disp == summary["dispatches"]
    json.dumps(m, allow_nan=False)


def test_xla_annotations_smoke():
    """xla_annotations=True with a tracer must run end-to-end (a
    TraceAnnotation around every host phase) without changing tokens."""
    cfg, params = materialize("phi4-mini-3.8b")
    base, _ = run_workload(cfg, params, horizon=4)
    tracer = Tracer()
    notes, _ = run_workload(cfg, params, horizon=4, xla_annotations=True,
                            tracer=tracer)
    tracer.detach()
    assert base == notes
    assert any(ev.cat == "host" for ev in tracer.events())


# ---------------------------------------------------------------------------
# satellite 2: speculative verify-overhead energy billing
# ---------------------------------------------------------------------------

def test_spec_overhead_rows_billed_per_request_and_in_phases():
    """Rejected draft rows are real forward passes: the per-request ODIN bill
    must exceed the naive prefill+emitted count by exactly the request's
    spec_overhead_rows, and the phase breakdown must sum to odin_total."""
    wspec = mixed_spec(pattern_period=8, prompt_buckets=(32,),
                       gen_buckets=(40,), n_requests=4)
    cfg, params = materialize("phi4-mini-3.8b")
    _, summary = run_workload(cfg, params, max_len=80, block_size=8,
                              spec=wspec, horizon=4, spec_ngram=4)
    st = summary["engine_stats"]
    assert st["spec_drafted"] > 0
    assert st["spec_overhead_rows"] > 0               # some drafts rejected
    assert summary["speculation"]["overhead_rows"] == st["spec_overhead_rows"]
    per_req_overhead = 0
    for rec in summary["requests"]:
        naive = rec["prefill_tokens"] + max(0, rec["generated_tokens"] - 1)
        over = rec["odin"]["spec_overhead"]["rows"]
        assert rec["odin"]["tokens"] == naive + over
        assert rec["odin"]["spec_overhead"]["energy_mj"] >= 0
        per_req_overhead += over
    assert per_req_overhead == st["spec_overhead_rows"]
    phases = summary["odin_phases"]
    assert phases["spec_verify_overhead"]["rows"] == st["spec_overhead_rows"]
    assert sum(p["rows"] for p in phases.values()) == summary["odin_total"]["tokens"]
    assert sum(p["energy_mj"] for p in phases.values()) == pytest.approx(
        summary["odin_total"]["energy_mj"])


def test_spec_off_overhead_is_zero():
    cfg, params = materialize("phi4-mini-3.8b")
    _, summary = run_workload(cfg, params, horizon=4)
    assert summary["engine_stats"]["spec_overhead_rows"] == 0
    for rec in summary["requests"]:
        assert rec["odin"]["spec_overhead"] == {"rows": 0, "energy_mj": 0.0}
