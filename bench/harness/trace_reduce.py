"""Reduce a profiler trace (``.xplane.pb``) to device busy, idle and op time.

The measured window is marked on the host by a ``bench/window`` trace
annotation, so the device's events and the window share the profiler's
clock.  On each TPU device plane, the ``XLA Ops`` line holds one event per
operation run, named by its HLO text; a loop's event (``while``) encloses
the events of its body.  Busy time is the union of those events' intervals
inside the window; idle is the rest.  An op's time is its self time: its
interval less the intervals of the ops it encloses, so nothing counts
twice.  Ops are named by their HLO instruction name (``paged_attention.6``).
Each idle gap is labelled with what the host was doing at its midpoint: the
program's ``serving/<kind>`` dispatch annotation in progress, or, where
none is, the step loop between dispatches (scheduling, emission, the front
door and the clients).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench/window"
OPS_LINE = "XLA Ops"
DISPATCH = re.compile(r"^serving/")
BETWEEN = "host: step loop between dispatches"


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over the device planes
    op_seconds: Dict[str, float]        # op name → self seconds (summed)
    gaps: List[Tuple[str, float]]       # (host activity, seconds), longest first

    def kernel_seconds(self, pattern: str) -> float:
        """Device seconds of every op whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_seconds.items() if rx.search(n))

    def top_ops(self, n: int = 10) -> List[List]:
        items = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in items]


def latest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def op_name(hlo: str) -> str:
    """``%paged_attention.6 = bf16[...] custom-call(...)`` → ``paged_attention.6``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def self_times(ivs: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Self time per name of properly nested intervals (start, end, name)."""
    out: Dict[str, float] = {}
    stack: List[Tuple[float, str]] = []        # (end, name) of open ops
    for s, e, name in sorted(ivs, key=lambda iv: (iv[0], -iv[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out[name] = out.get(name, 0.0) + (e - s)
        if stack:                              # enclosed: not the parent's
            parent = stack[-1][1]
            out[parent] -= min(e, stack[-1][0]) - s
        stack.append((e, name))
    return out


def reduce_planes(planes, n_gaps: int = 10) -> Reduced:
    """Reduce ``ProfileData`` planes (or any objects with the same fields)."""
    window: Optional[Tuple[float, float]] = None
    host_spans: List[Tuple[float, float, str]] = []
    devices = []
    for pl in planes:
        if pl.name.startswith("/device:TPU:") and "SparseCore" not in pl.name:
            devices.append(pl)
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                for ev in ln.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif DISPATCH.match(ev.name):
                        host_spans.append((ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           ev.name))
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    if not devices:
        raise ValueError("trace has no TPU device plane")
    w0, w1 = window
    op_ns: Dict[str, float] = {}
    busy_ns = []
    all_gaps: List[Tuple[float, float]] = []
    for pl in devices:
        ivs = []
        for ln in pl.lines:
            if ln.name != OPS_LINE:
                continue
            for ev in ln.events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
                if e > s:
                    ivs.append((s, e, op_name(ev.name)))
        for k, v in self_times(ivs).items():
            op_ns[k] = op_ns.get(k, 0.0) + v
        busy = merge([(s, e) for s, e, _ in ivs])
        busy_ns.append(sum(e - s for s, e in busy))
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        all_gaps.extend((edges[i], edges[i + 1])
                        for i in range(0, len(edges), 2)
                        if edges[i + 1] > edges[i])
    host_spans.sort()

    def label(mid: float) -> str:
        for s, e, name in host_spans:
            if s <= mid < e:
                return f"host: in {name} dispatch"
        return BETWEEN

    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:n_gaps]
    gaps = [(label((s + e) / 2), (e - s) * 1e-9) for s, e in longest]
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy_ns) / len(busy_ns) * 1e-9,
                   op_seconds={k: v * 1e-9 for k, v in op_ns.items()},
                   gaps=gaps)


def reduce_file(path: str, n_gaps: int = 10) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, n_gaps)
