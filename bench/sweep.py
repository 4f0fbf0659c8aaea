#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <name> --rates 1,1.5,2 --seconds 30

One process on the chip; one run of the cell per rate, the traffic file's
other parameters as they are.  For each rate it prints the backlog (requests
due at least ``WAITING_S`` earlier and not yet given a first token) at the
first quarter and at the end of the window, and the client-side tails.  The knee is the highest rate whose
backlog at the end is no longer than at the first quarter; the cell then
runs at about four fifths of it (``rate_rps`` in its traffic file).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run as runmod  # noqa: E402
from harness import endtoend  # noqa: E402
from harness.spec import load_cell  # noqa: E402


# a request counts as waiting once it is this much past its due time with no
# first token: under the knee a first token comes in a few tenths of a
# second, so a request due just before the window's close is not a queue
WAITING_S = 1.0


def backlog(recs, t: float) -> int:
    return sum(1 for r in recs if r.due <= t - WAITING_S
               and (r.first is None or r.first > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("the knee is a property of an open-loop cell")
    for rate in (float(r) for r in args.rates.split(",")):
        c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                   rate_rps=rate))
        res = runmod.run(c, args.seed, args.seconds, False, limits={},
                         t_start=time.perf_counter(), keep_log=True)
        recs, win = res["log"]
        q1 = win.open_at + 0.25 * win.seconds
        m = endtoend.compute(["ttft_p50_ms", "itl_p99_ms", "output_tok_s"],
                             recs, win)
        print(json.dumps({"rate_rps": rate,
                          "backlog_q1": backlog(recs, q1),
                          "backlog_end": backlog(recs, win.close_at),
                          "attempted": res["attempted"],
                          "failed": res["failed"], **m}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
