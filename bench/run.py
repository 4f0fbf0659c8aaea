#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process on the cell's chips, in this order:

  1. compile cache   JAX's persistent cache inside the checkout (or where
                     ``JAX_COMPILATION_CACHE_DIR`` says), every program kept
  2. device          the platform must be ``tpu`` with the cell's chips;
                     otherwise exit 2 and print no result
  3. weights         made on the device from ``--seed`` in one jitted call,
                     in the layout of the program's configuration that the
                     model family (``bench/families/``) makes of the
                     configuration file
  4. engine          the program's ``ServingEngine`` with the configuration
                     file's settings, behind its ``FrontDoor``
  5. warm-up         the decode program and every mixed tile width
  6. ramp            the cell's traffic until the slots are in steady state
  7. window          ``--seconds`` of the same traffic, measured from the
                     client side (with ``--trace 1``: profiled, and the
                     program's tracer on)
  8. correctness     a sample of finished requests, drawn from the seed,
                     against the family's plain float32 reference
  9. result          the last line of stdout, one JSON object

Set-up (``setup_s``) is everything before the window opens, ramp included.
The checks compared, each beside its limit, are the last lines on stderr
and the last key of the result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import numpy as np  # noqa: E402

from harness import endtoend  # noqa: E402
from harness.spec import (Cell, SpecError, load_cell, metric_reader,  # noqa: E402
                          peaks)
from harness.traffic import Traffic, check_fits  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SAMPLE_TOKENS = 256        # served tokens the correctness sample reaches
SAMPLE_MAX = 16            # requests in the sample at most
REF_BATCH = 4              # sequences per group in the reference
TRACER_CAPACITY = 1 << 20
ENGINE_LOG = ("steps", "decode_steps", "mixed_dispatches", "decode_tokens",
              "active_slot_steps", "slot_steps", "mixed_prefill_deferred",
              "preempt_swap", "preempt_recompute", "host_plan_s",
              "dispatch_sync_s", "gc_pause_s", "cow_forks")  # logged every run


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


class Watchdog(threading.Thread):
    """Wakes every ``tick`` seconds through the window and keeps its latest
    wake-up: a stall it shares says the whole process stood still (held off
    the CPU, or the interpreter lock held), one it does not says the main
    thread waited inside a call."""

    def __init__(self, tick: float = 0.01):
        super().__init__(daemon=True)
        self.tick, self.late, self.at = tick, 0.0, 0.0
        self.stop = threading.Event()

    def run(self):
        t0 = t = time.perf_counter()
        while not self.stop.wait(self.tick):
            now = time.perf_counter()
            if now - t - self.tick > self.late:
                self.late, self.at = now - t - self.tick, t - t0
            t = now


class Clock:
    """``time.perf_counter`` that remembers its first reading, so the
    engine's clock (which starts at its first call) maps onto the
    benchmark's."""

    def __init__(self):
        self.first = None

    def __call__(self) -> float:
        t = time.perf_counter()
        if self.first is None:
            self.first = t
        return t


def device_info(jax, chips: int, require_chip: bool) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and (info["platform"] != "tpu" or info["count"] < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX finds {info}")
    return info


def load_limits(workload: str) -> dict:
    path = os.path.join(BENCH, "limits", workload + ".json")
    if not os.path.isfile(path):
        return {}
    with open(path) as f:
        return json.load(f)


def draw_sample(recs, seed: int, min_requests: int = 1):
    """Finished requests for the reference: the longest, then others drawn
    from the seed, until ``SAMPLE_TOKENS`` served tokens and ``min_requests``
    requests (the limits file's ``sample_requests``, so that the sample
    spans several slots), or ``SAMPLE_MAX`` requests."""
    done = [r for r in recs if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.max_new, r.i))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed) & 0xFFFFFFFF, 5]).permutation(
        len(rest))
    sample = [longest]
    for j in order:
        if ((sum(len(r.tokens) for r in sample) >= SAMPLE_TOKENS
                and len(sample) >= min_requests)
                or len(sample) >= SAMPLE_MAX):
            break
        sample.append(rest[j])
    return sample


def gap_numbers(gap) -> dict:
    """The numbers compared of one reading: the widest gap by which a served
    token's logit lies below the reference's best, and the mean gap over
    all served tokens."""
    if gap is None or not len(gap):
        return {"max_logit_gap": None, "mean_logit_gap": None}
    gap = np.asarray(gap, np.float64)
    return {"max_logit_gap": float(gap.max()),
            "mean_logit_gap": float(gap.mean())}


def compare(gap, limits: dict, failed: int, short: int):
    """``(checks, correct)`` of one reading: each number beside its limit
    from the cell's limits file (``None`` where the file sets none).  Correct
    when every number that has a limit is at or under it, at least one has,
    and no request failed or ended short."""
    checks = {}
    for name, value in gap_numbers(gap).items():
        checks[name] = {"value": value,
                        "limit": limits.get(name, {}).get("limit")}
    compared = [c for c in checks.values() if c["limit"] is not None]
    correct = (bool(compared)
               and all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in compared))
    checks["failed_requests"] = {"value": failed, "limit": 0}
    checks["short_streams"] = {"value": short, "limit": 0}
    return checks, correct and not failed and not short


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, cache: bool = True, limits=None,
        controls=(), t_start: float = None,
        keep_log: bool = False) -> dict:
    """Phases 1-8 of one run; returns the result object (without printing).
    ``controls`` (precisions, for calibration) adds the reference's gaps and
    each control's under ``"control"``, and each control put in the
    program's place through the same comparison under ``"control_checks"``;
    ``keep_log`` adds the client log and window under ``"log"``."""
    t_start = T_START if t_start is None else t_start
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from harness import program, reference, weights
    from harness.client import drive
    from harness.trace_reduce import latest_xplane, reduce_file

    if cache:                                                        # 1
        log(f"compile cache: {program.use_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **_: compiles.append(time.perf_counter())
        if ev == COMPILE_EVENT else None)
    info = device_info(jax, cell.chips, require_chip)                # 2
    log(f"device {info}")

    config, mix = cell.config, cell.traffic
    hf, eng_cfg = config["hf_config"], config["engine"]
    check_fits(mix, int(eng_cfg["max_len"]))
    family = cell.family
    cfg = family.model_config(config)
    params = weights.make_params(program.weight_layout(cfg), seed,  # 3
                                 draw=getattr(family, "draw", None))
    jax.block_until_ready(params)
    log(f"weights {time.perf_counter() - t_start:.2f} s after start")

    tracer = program.Tracer(capacity=TRACER_CAPACITY) if trace else None
    clk = Clock()
    engine = program.build_engine(cfg, params, eng_cfg, tracer=tracer,  # 4
                                  clock=clk, annotate=trace)
    tiles = program.warm_up(engine, eng_cfg, seed)                   # 5
    log(f"warmed decode + mixed tiles {tiles}; "
        f"{time.perf_counter() - t_start:.2f} s after start")

    traffic = Traffic(mix, seed, hf["vocab_size"])
    fd = program.FrontDoor(engine, max_queue=1 << 30)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    marks = {}

    def on_open():
        marks["open"] = time.perf_counter()
        marks["compiles"] = len(compiles)
        marks["stats0"] = dataclasses.asdict(engine.stats)
        jax.config.update("jax_log_compiles", True)  # names what compiles late
        marks["watchdog"] = Watchdog()
        marks["watchdog"].start()
        if trace:
            jax.profiler.start_trace(trace_dir)
            marks["ann"] = jax.profiler.TraceAnnotation("bench/window")
            marks["ann"].__enter__()

    def on_close():
        marks["close"] = time.perf_counter()
        marks["compiles_in"] = len(compiles) - marks["compiles"]
        jax.config.update("jax_log_compiles", False)
        marks["watchdog"].stop.set()
        marks["watchdog"].join()
        marks["stats1"] = dataclasses.asdict(engine.stats)
        if trace:
            marks["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    recs, win = asyncio.run(drive(                                   # 6, 7
        fd, traffic, program.Request, ramp_s=float(mix["ramp_s"]),
        seconds=seconds, on_open=on_open, on_close=on_close))
    setup_s = marks["open"] - t_start
    log(f"setup {setup_s:.3f} s; window {win.seconds:.3f} s; "
        f"{marks['compiles_in']} compilations inside the window")
    if traffic.loop == "open":
        late = np.array([r.sent - r.due for r in recs
                         if endtoend.in_window(r.due, win)]) * 1e3
        if late.size:
            log(f"generator lateness over {late.size} submits: p50 "
                f"{np.percentile(late, 50):.2f} ms, p99 "
                f"{np.percentile(late, 99):.2f} ms, max {late.max():.2f} ms")
    else:
        log("generator lateness: closed loop, no schedule to be late for")
    log("client: " + endtoend.describe(recs, win))

    stats = {k: marks["stats1"][k] - marks["stats0"][k]
             for k in marks["stats0"]
             if isinstance(marks["stats0"][k], (int, float))}
    log("engine in the window: " + ", ".join(
        f"{k} {stats.get(k, 0):.6g}" for k in ENGINE_LOG))
    log(f"watchdog: latest wake-up {1e3 * marks['watchdog'].late:.1f} ms "
        f"late, {marks['watchdog'].at:.2f} s into the window")
    spans = []
    if tracer is not None:
        if tracer.dropped_events:
            raise RuntimeError(f"tracer dropped {tracer.dropped_events} "
                               f"events; raise TRACER_CAPACITY")
        for ev in tracer.events():
            if ev.ph == "X" and ev.cat == "dispatch":
                t = ev.ts + clk.first - win.t0
                if endtoend.in_window(t, win):
                    spans.append(SimpleNamespace(name=ev.name, ts=t,
                                                 dur=ev.dur, args=ev.args))
    mem = jax.devices()[0].memory_stats() or {}
    info["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))

    attempted = endtoend.attempted(recs, win)
    failed = endtoend.failed(recs, win)
    limits = load_limits(cell.name) if limits is None else limits
    sample = draw_sample(recs, seed, int(limits.get("sample_requests", 1)))
    del fd, engine, tracer                                           # 8
    gc.collect()
    t_ref = time.perf_counter()
    ref = None
    if sample:
        seqs = [(r.prompt, np.asarray(r.tokens, np.int32)) for r in sample]
        ref = reference.logit_gaps(family.forward_rows, params, hf, seqs,
                                   controls, batch=REF_BATCH)
    log(f"reference over {len(sample)} requests, "
        f"{sum(len(r.tokens) for r in sample)} served tokens: "
        f"{time.perf_counter() - t_ref:.2f} s")
    short = sum(1 for r in recs if r.state == "done" and not r.finished)
    checks, correct = compare(None if ref is None else ref["gap"], limits,
                              len(failed), short)

    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": len(failed), "metrics": {}, "device": info}
    if not trace:
        names = [m["name"] for m in cell.end_to_end]
        vals = endtoend.compute(names, recs, win)
        vals["setup_s"] = setup_s
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": vals[m["name"]],
                                            "unit": m["unit"]}
    else:
        red = reduce_file(latest_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        info["busy_s"] = red.busy_s
        info["window_s"] = red.window_s
        record = SimpleNamespace(recs=recs, window=win, spans=spans,
                                 stats=stats, trace=red, hf=hf,
                                 engine=eng_cfg, peaks=peaks(info["kind"])
                                 if require_chip else None, flops=family)
        for m in cell.per_layer:
            v = metric_reader(m["name"])(record)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": [list(g) for g in red.gaps]}
    if ref is not None and controls:
        result["control"] = {k: v.tolist() for k, v in ref.items()}
        result["control_checks"] = {
            low: dict(zip(("checks", "correct"), compare(
                ref["control_gap/" + low], limits, 0, 0)))
            for low in controls}
    if keep_log:
        result["log"] = (recs, win)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except (NoChip, SpecError) as exc:
        log(f"refused: {exc}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
