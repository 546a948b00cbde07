#!/usr/bin/env python3
"""Readings that set the limits of an LLM cell's numbers: for each seed,
the program's readings (its K/V rows and served tokens against the
float32 reference) and the float8 control's (its own rows, and the token
it puts first, on the same tokens), over the same sample a run compares.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

One process, one engine per seed (the weights come from the seed); the
window is shorter than a run's but at the cell's own load.  Prints one
JSON line per seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--preroll", type=float, default=20.0)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args()
    spec = harness.load_spec()
    cell = harness.cell(spec, args.workload)
    cfg = harness.data_file("configs", cell["config"])
    traffic = harness.data_file("traffic", cell["traffic"])
    harness.enable_cache()
    harness.require_chips(int(cell["chips"]))
    import repro  # noqa: F401
    import llm

    ref = harness.module("reference", cell["config"])
    for seed in (int(x) for x in args.seeds.split(",")):
        E = llm.Engine(cfg, seed)
        E.warm_llm()
        E.snapshot()
        W = llm.run_window(E, dict(traffic, preroll_s=args.preroll),
                           seed, args.seconds)
        sample = llm.sample_finished(W, E, seed)
        E.free()
        t = time.perf_counter()
        row = {"seed": seed, "requests": len(sample),
               "program": ref.readings(cfg, seed, sample),
               "reference_s": time.perf_counter() - t}
        if not args.no_control:
            row["control"] = ref.readings(cfg, seed, sample, control=True)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
