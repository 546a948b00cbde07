#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic by name, draws the weights and
the traffic from ``--seed``, warms up the cell's own shapes (set-up), then
drives the system for ``--seconds`` and prints, as the last line of
standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device`` and ``checks`` (each
number compared, beside its limit; they are also the last lines of
standard error).  With no TPU, or fewer chips than the cell asks for, it
exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402


def end_to_end(W, setup_s) -> dict:
    """Every end-to-end number this run can give, by name."""
    return {"setup_s": setup_s,
            "tokens_per_s": harness.tokens_in(W.reqs, 0.0, W.t_close)
            / W.t_close}


def info_line(cell, W, e2e) -> dict:
    """The earlier output line: how late the generator ran, the tails,
    which are not judged in a cell above the knee, and the window's
    counts."""
    late = W.lateness or [0.0]
    ttft = harness.ttft_samples(W.reqs, W.t_close)
    itl = harness.itl_samples(W.reqs, 0.0, W.t_close)
    return {"workload": cell["name"], "window_s": W.t_close,
            "generator_late_ms": {
                "p50": 1e3 * harness.percentile(late, 50),
                "p99": 1e3 * harness.percentile(late, 99),
                "max": 1e3 * max(late)},
            "requests": len(W.reqs), "engine_steps": len(W.steps),
            "admit_s": W.admit_s,
            "decode_s": sum(s["t1"] - s["t0"] for s in W.steps),
            "e2e": e2e,
            "finished": sum(1 for r in W.reqs if "done" in r),
            "ttft_n": len(ttft), "itl_n": len(itl),
            "ttft_ms": {q: 1e3 * harness.percentile(ttft, q)
                        for q in (50, 90, 95, 99)},
            "itl_ms": {q: 1e3 * harness.percentile(itl, q)
                       for q in (50, 95, 99)},
            "counters": W.counters}


def checks_of(got: dict, ref, fp_failed: int, retraces: int) -> list:
    """(name, number, limit, ok) for each number compared: the K/V rows
    and served tokens against the reference (``got`` is its
    ``readings``), the engine's fingerprint failures and retraces."""
    def at_most(name, limit):
        v = got.get(name)
        return (name, v, limit, v is not None and v <= limit)

    rows = got.get("kv_rows", 0)
    return [at_most("kv_prompt_err", ref.KV_PROMPT_LIMIT),
            at_most("kv_decode_err", ref.KV_DECODE_LIMIT),
            at_most("max_logit_gap", ref.GAP_LIMIT),
            ("rows_compared", rows, 1, rows >= 1),
            ("fingerprint_failures", fp_failed, 0, fp_failed == 0),
            ("retraces", retraces, 0, retraces == 0)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = harness.load_spec()
        cell = harness.cell(spec, args.workload)
        cfg = harness.data_file("configs", cell["config"])
        traffic = harness.data_file("traffic", cell["traffic"])
        import jax

        harness.enable_cache()
        device = harness.require_chips(int(cell["chips"]))
        peaks = harness.peaks(device["kind"])
        import repro  # noqa: F401  (the program's numeric settings)
    except (harness.SetupError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return run_cell(args, spec, cell, cfg, traffic, device, peaks)


def run_cell(args, spec, cell, cfg, traffic, device, peaks) -> int:
    import jax
    import llm

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(harness.ROOT, ".bench_trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    E = llm.Engine(cfg, args.seed)
    E.warm_llm()
    E.snapshot()
    W = llm.run_window(E, traffic, args.seed, args.seconds,
                       trace_dir=trace_dir)
    # set-up: process start until the window opens (pre-roll included)
    setup_s = W.open_clock - T_START
    device["memory_peak_bytes"] = harness.memory_peak(
        jax.devices()[:device["count"]])

    # -- the comparison that decides `correct`, with the engine freed
    sample = llm.sample_finished(W, E, args.seed)
    fp_failed, retr = E.verify_failed, E.retraces()
    E.free()
    ref = harness.module("reference", cell["config"])
    t_ref = time.perf_counter()
    got = ref.readings(cfg, args.seed, sample) if sample else {}
    checks = checks_of(got, ref, fp_failed, retr)
    correct = all(c[3] for c in checks)
    attempted = sum(1 for r in W.reqs if 0 <= r["due"] < W.t_close)

    e2e = end_to_end(W, setup_s)
    info = info_line(cell, W, e2e)
    info["reference_s"] = time.perf_counter() - t_ref
    info["compared"] = {k: got.get(k) for k in (
        "kv_prompt_by_layer", "kv_decode_by_layer", "tokens", "altered_gap")}
    breakdown = None
    if args.trace:
        import trace_reduce

        path = trace_reduce.find_xplane(trace_dir)
        tr = trace_reduce.Trace.from_file(path) if path else None
        span = W.trace_span
        work = None
        if os.path.isfile(os.path.join(harness.BENCH, "work",
                                       cfg["name"] + ".py")):
            work = harness.module("work", cfg["name"])
        # what a per-layer metric's reader is handed
        run = types.SimpleNamespace(window=W, trace=tr, trace_span=span,
                                    spec=cfg, peaks=peaks, work=work,
                                    cell=cell)
        metrics = {}
        for m in harness.cell_metrics(spec, cell["name"], trace=True):
            v = harness.metric_reader(m["name"]).read(run)
            if harness.finite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s() if tr else 0.0
        device["window_s"] = span[1] - span[0] if span else 0.0
        if tr is not None:
            breakdown = {"device_ops": tr.top_modules(10),
                         "idle_gaps": tr.idle_gaps(10)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in harness.cell_metrics(spec, cell["name"], False)}
    print(json.dumps({"info": info}, default=float), flush=True)
    harness.print_checks(checks)
    print(harness.result_line(correct=correct, attempted=attempted,
                              failed=0, metrics=metrics, device=device,
                              checks=checks, breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
