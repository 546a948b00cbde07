#!/usr/bin/env python3
"""Find the knee of a cell's mix once, on the chip: the highest offered
rate the system sustains without a growing backlog.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1,1.5,2

Runs the cell's open loop at each rate in one process (one set-up), and
prints one JSON line per rate: requests due, admitted, still queued at
the close, tokens/s and the time-to-first-token quantiles.  The rate the
cell's mix file then holds is chosen from this table by hand, and the
table goes into PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    spec = harness.load_spec()
    cell = harness.cell(spec, args.workload)
    cfg = harness.data_file("configs", cell["config"])
    traffic = harness.data_file("traffic", cell["traffic"])
    harness.enable_cache()
    harness.require_chips(int(cell["chips"]))
    import repro  # noqa: F401
    import llm

    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        # a fresh engine per rate: no request of one rate outlives it
        E = llm.Engine(cfg, args.seed)
        E.warm_llm()
        E.snapshot()
        W = llm.run_window(E, dict(traffic, rate_per_s=rate), args.seed + i,
                           args.seconds)
        win = W.t_close
        due = [r for r in W.reqs if 0 <= r["due"] < win]
        ttft = harness.ttft_samples(W.reqs, win)
        row = {"rate": rate, "window_s": win, "due": len(due),
               "admitted": sum(1 for r in due if r["times"]),
               "queued_at_close": sum(1 for r in due if not r["times"]),
               "finished": sum(1 for r in due if "done" in r),
               "tokens_per_s": harness.tokens_in(W.reqs, 0, win) / win,
               "ttft_ms": {q: 1e3 * harness.percentile(ttft, q)
                           for q in (50, 90, 99)},
               "itl_p95_ms": 1e3 * harness.percentile(
                   harness.itl_samples(W.reqs, 0, win), 95),
               "deferrals": W.counters["deferrals"],
               "retraces": E.retraces()}
        print(json.dumps(row), flush=True)
        E.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
