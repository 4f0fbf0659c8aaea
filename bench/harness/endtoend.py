"""End-to-end metrics, from the client log alone.

Every time here is when the benchmark's consumer received an event, on the
benchmark's clock.  A tail is over all requests of the window; a rate is
over all the work and all the time of the window.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from harness.client import WAIT_S, Rec, Window


def in_window(t: float, win: Window) -> bool:
    return win.open_at <= t < win.close_at


def attempted(recs: List[Rec], win: Window) -> List[Rec]:
    """Requests due (open loop) or sent (closed loop) inside the window."""
    return [r for r in recs if in_window(r.due, win)]


def failed(recs: List[Rec], win: Window) -> List[Rec]:
    """Refused, ended by the program in another state than ``done``, or
    never given a first token while the benchmark waited."""
    return [r for r in attempted(recs, win)
            if r.error is not None or r.state not in (None, "done")
            or r.first is None]


def ttft_p50_ms(recs, win, **_) -> float:
    """Median, over requests due in the window, of due time to first token
    received; one never answered counts as the whole wait."""
    vals = [((r.first if r.first is not None else win.close_at + WAIT_S)
             - r.due) for r in attempted(recs, win)]
    return float(np.percentile(vals, 50) * 1e3)


def itl_p99_ms(recs, win, **_) -> float:
    """99th percentile of every gap between consecutive tokens of a request
    whose later token was received in the window."""
    gaps = [b - a for r in recs for a, b in zip(r.times, r.times[1:])
            if in_window(b, win)]
    return float(np.percentile(gaps, 99) * 1e3)


def output_tok_s(recs, win, **_) -> float:
    n = sum(1 for r in recs for t in r.times if in_window(t, win))
    return n / win.seconds


def prompt_tok_s(recs, win, **_) -> float:
    """Prompt tokens per second of the window.  A request's prompt is
    credited evenly over the time from its send to its first token, and the
    window takes the part of that span that lies inside it, so that no
    request's whole prompt falls in or out with the window's edges."""
    n = 0.0
    for r in recs:
        if r.first is None:
            continue
        if r.first <= r.sent:
            n += r.prompt_len * in_window(r.first, win)
            continue
        inside = (min(r.first, win.close_at) - max(r.sent, win.open_at))
        n += r.prompt_len * max(0.0, inside) / (r.first - r.sent)
    return n / win.seconds


def describe(recs: List[Rec], win: Window) -> str:
    """Counts and quantiles of the window, for the run's log."""
    due = attempted(recs, win)
    ttft = [r.first - r.due for r in due if r.first is not None]
    gaps = [b - a for r in recs for a, b in zip(r.times, r.times[1:])
            if in_window(b, win)]

    def q(v, ps):
        return ("/".join(f"{np.percentile(v, p) * 1e3:.1f}" for p in ps)
                if v else "none")
    return (f"{len(due)} requests in the window, {len(ttft)} answered; "
            f"TTFT p50/p75/p90 {q(ttft, (50, 75, 90))} ms; "
            f"{len(gaps)} token gaps, p50/p95/p99 {q(gaps, (50, 95, 99))} ms")


METRICS = {f.__name__: f for f in
           (ttft_p50_ms, itl_p99_ms, output_tok_s, prompt_tok_s)}


def compute(names: List[str], recs: List[Rec], win: Window) -> Dict[str, float]:
    unknown = [n for n in names if n not in METRICS and n != "setup_s"]
    if unknown:
        raise KeyError(f"no end-to-end metric named {unknown}")
    return {n: METRICS[n](recs, win) for n in names if n in METRICS}
