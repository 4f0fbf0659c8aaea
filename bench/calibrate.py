#!/usr/bin/env python3
"""Read the correctness numbers of a cell on many seeds, with its controls.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 10 \
        [--out readings.jsonl]
    python3 bench/calibrate.py --workload <name> --judge readings.jsonl

One process on the chip, one short run of the cell per seed at the cell's
own load and sizes (the timed path, as ``bench/run.py`` drives it), each
followed by the reference and the controls over the same sample: the same
reference with its matrix products in int8, and in fp8 (e4m3).  Each
control is put in the program's place and judged by the benchmark's own
comparison (``run.compare``) against the cell's limits file.  Prints one JSON
line per seed and a summary: for each number compared, the program's
largest reading over the seeds (the lower reading) and each control's
smallest (the upper reading).  ``--out`` keeps every served position's gaps,
and ``--judge`` applies the limits file as it stands to such a file again,
without a chip.  The benchmark's own runs do not run the controls.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run as runmod  # noqa: E402
from harness.spec import load_cell  # noqa: E402

CONTROLS = ("int8", "fp8")


def judge(rows, limits) -> dict:
    """Per-seed readings of the program and of each control, each through
    ``run.compare`` with ``limits``; and the summary over the seeds."""
    out = []
    for row in rows:
        prog, correct = runmod.compare(row["gap"], limits, row["failed"],
                                       row["short_streams"])
        line = {"seed": row["seed"], "served_tokens": len(row["gap"]),
                "program": {k: c["value"] for k, c in prog.items()},
                "program_correct": correct}
        for low in CONTROLS:
            checks, ok = runmod.compare(row["control_gap/" + low], limits, 0, 0)
            line[low] = {k: checks[k]["value"] for k in runmod.gap_numbers(None)}
            line[low + "_correct"] = ok
        out.append(line)
    names = list(runmod.gap_numbers(None))
    summary = {
        "lower": {k: max(r["program"][k] for r in out) for k in names},
        "upper": {low: {k: min(r[low][k] for r in out) for k in names}
                  for low in CONTROLS},
        "program_correct": all(r["program_correct"] for r in out),
        "control_correct": {low: [r[low + "_correct"] for r in out]
                            for low in CONTROLS},
        "limits": {k: limits.get(k, {}).get("limit") for k in names},
        "seeds": len(out)}
    return {"seeds": out, "summary": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", help="keep every reading here (JSON lines)")
    ap.add_argument("--judge", help="judge the readings kept in this file")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    limits = runmod.load_limits(cell.name)
    if args.judge:
        with open(args.judge) as f:
            rows = [json.loads(line) for line in f if line.strip()]
    else:
        rows = []
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            res = runmod.run(cell, seed, args.seconds, False, t_start=t0,
                             limits=limits, controls=CONTROLS)
            row = {"seed": seed, "failed": res["failed"],
                   "short_streams": res["checks"]["short_streams"]["value"],
                   "gap": res["control"]["gap"],
                   **{"control_gap/" + low: res["control"]["control_gap/" + low]
                      for low in CONTROLS}}
            rows.append(row)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
            line = judge([row], limits)["seeds"][0]
            line["seconds"] = round(time.perf_counter() - t0, 1)
            print(json.dumps(line), flush=True)
    print(json.dumps(judge(rows, limits)["summary"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
