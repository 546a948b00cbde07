"""Small versions of the benchmark's configurations and mixes, and a way
to drive a whole run on the CPU with the chip check skipped."""
from __future__ import annotations

import copy
import io
import json
import os
import sys
from contextlib import redirect_stderr, redirect_stdout

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12,
         "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def qwen_smoke(**engine) -> dict:
    """Qwen1.5-MoE's file at a CPU size: 2 layers, d 64, 8 experts top-2,
    vocab 512, float32."""
    s = copy.deepcopy(harness.data_file("configs", "qwen1.5-moe-a2.7b"))
    s.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             num_hidden_layers=2, vocab_size=512, num_experts=8,
             num_experts_per_tok=2, moe_intermediate_size=32,
             shared_expert_intermediate_size=64)
    s["engine"].update(param_dtype="float32", dtype="float32",
                       capacity_factor=4.0, n_slots=4, cache_len=256,
                       page_size=16, prefill_chunk=16, n_pages=0,
                       bucket_lo=16)
    s["engine"].update(engine)
    return s


def qwen_control() -> dict:
    """A CPU size at which the float8 control's rounding adds up as it
    does at the cell's: the cell's 8 layers and top-4 routing, d 128."""
    s = qwen_smoke()
    s.update(hidden_size=128, num_hidden_layers=8, num_experts=16,
             num_experts_per_tok=4, moe_intermediate_size=64,
             shared_expert_intermediate_size=256)
    return s


def chat_smoke() -> dict:
    t = copy.deepcopy(harness.data_file("traffic", "chat-over"))
    t.update(rate_per_s=8.0, block=4, preroll_s=0.5,
             prompt={"median": 24, "sigma": 1.0, "min": 8, "max": 120},
             output={"median": 6, "sigma": 0.5, "min": 3, "max": 12})
    return t


def run_smoke(cell_name: str, cfg: dict, traffic: dict, *, seed: int = 7,
              seconds: float = 3.0, trace: int = 0):
    """One whole run of ``cell_name`` with the given files, on the CPU:
    returns (result dict, stderr text)."""
    import run as bench_run

    spec = harness.load_spec()
    # a mix that no cell of BENCHMARK.json runs yet is driven as a cell
    cell = next((w for w in spec["workloads"] if w["name"] == cell_name),
                {"name": cell_name, "config": cfg["name"], "chips": 1})
    args = type("A", (), dict(seed=seed, seconds=seconds, trace=trace))
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = bench_run.run_cell(args, spec, cell, cfg, traffic, device, PEAKS)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()
