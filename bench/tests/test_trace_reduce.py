"""The trace reduction against hand counts: a hand-built trace, and a small
trace recorded on a TPU v5e (``bench/fixtures/v5e_small.xplane.pb``)."""
import os
from types import SimpleNamespace as NS

import pytest

from harness import trace_reduce as tr
from harness.spec import BENCH_DIR

FIXTURE = os.path.join(BENCH_DIR, "fixtures", "v5e_small.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def test_hand_built_trace():
    # window [100, 1100) ns; one op straddles each edge; a loop encloses
    # the ops of its body (the device runs one op at a time)
    host = plane("/host:CPU", python=[
        ev("bench/window", 100, 1000),
        ev("serving/decode", 150, 300),
        ev("serving/mixed", 600, 300)])
    dev = plane("/device:TPU:0", XLA_Ops=[
        ev("%fusion.1 = f32[8] fusion(...)", 50, 130),   # [100, 180): 80
        ev("%paged_attention.6 = bf16[8] custom-call(...)", 180, 100),
        ev("%while.1 = (s32[]) while(...)", 480, 300),   # [480, 780)
        ev("%fusion.2 = bf16[8] fusion(...)", 500, 140),  # in while.1
        ev("%paged_attention.6 = bf16[8] custom-call(...)", 650, 100),
        ev("%fusion.4 = bf16[8] fusion(...)", 760, 5),    # in while.1
        ev("%fusion.3 = f32[8] fusion(...)", 1050, 200)],  # [1050, 1100)
        Steps=[ev("step", 0, 5000)])      # other lines are not ops
    red = tr.reduce_planes([host, dev], n_gaps=3)
    assert red.window_s == pytest.approx(1000e-9)
    # busy = [100, 280) ∪ [480, 780) ∪ [1050, 1100) = 180 + 300 + 50
    assert red.busy_s == pytest.approx(530e-9)
    assert red.kernel_seconds("^paged_attention") == pytest.approx(200e-9)
    assert red.op_seconds["fusion.1"] == pytest.approx(80e-9)
    # self time of the loop: 300 less its body's 140 + 100 + 5
    assert red.op_seconds["while.1"] == pytest.approx(55e-9)
    assert sum(red.op_seconds.values()) == pytest.approx(red.busy_s)
    # idle gaps: [280, 480) 200, midpoint 380 inside serving/decode;
    # [780, 1050) 270, midpoint 915 after serving/mixed ended at 900
    assert red.gaps[0] == ("host: step loop between dispatches",
                           pytest.approx(270e-9))
    assert red.gaps[1] == ("host: in serving/decode dispatch",
                           pytest.approx(200e-9))
    assert len(red.gaps) == 2
    assert red.top_ops(2) == [["paged_attention.6", pytest.approx(200e-9)],
                              ["fusion.2", pytest.approx(140e-9)]]


def test_no_window_is_an_error():
    dev = plane("/device:TPU:0", XLA_Ops=[ev("x", 0, 10)])
    with pytest.raises(ValueError):
        tr.reduce_planes([plane("/host:CPU", python=[]), dev])


def test_recorded_v5e_trace_matches_hand_count():
    from jax.profiler import ProfileData
    red = tr.reduce_file(FIXTURE)
    planes = ProfileData.from_file(FIXTURE).planes
    # hand count: a sweep over the op events of the TPU plane, 1 ns steps
    # collapsed into sorted edges, independent of tr.merge
    w0 = w1 = None
    ops = []
    for pl in planes:
        for ln in pl.lines:
            for e in ln.events:
                if pl.name.startswith("/host:") and e.name == "bench/window":
                    w0, w1 = e.start_ns, e.start_ns + e.duration_ns
                if pl.name == "/device:TPU:0" and ln.name == "XLA Ops":
                    ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    marks = []
    for s, e, _ in ops:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            marks += [(s, 1), (e, -1)]
    marks.sort(key=lambda m: (m[0], -m[1]))
    depth, busy, since = 0, 0.0, None
    for t, d in marks:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    kern = sum(min(e, w1) - max(s, w0) for s, e, n in ops
               if "paged_attention" in n and min(e, w1) > max(s, w0))
    assert red.window_s == pytest.approx((w1 - w0) * 1e-9)
    assert red.busy_s == pytest.approx(busy * 1e-9)
    assert red.kernel_seconds("paged_attention") == pytest.approx(kern * 1e-9)
    # read by hand: three kernel calls of 4148, 4085 and 4175 ns; the
    # first lies before the window on the device plane's clock, which in
    # this trace runs about 1.4 ms behind the host plane's
    assert kern == 4085 + 4175
    assert 0 < red.busy_s < red.window_s
