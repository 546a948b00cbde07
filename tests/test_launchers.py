"""Launcher integration: train driver resume-exactness, serve driver, and a
small-device-count dry-run lowering in a subprocess (so the 512-device
XLA_FLAGS never pollutes this process).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_train_driver_resumes_exactly(tmp_path):
    from repro.launch.train import main as train_main

    ck = str(tmp_path / "ck")
    train_main(["--arch", "gemma-2b", "--steps", "8", "--ckpt-dir", ck,
                "--save-every", "4", "--batch", "2", "--seq", "16"])
    # second run resumes from step 8's predecessor checkpoint and continues
    train_main(["--arch", "gemma-2b", "--steps", "10", "--ckpt-dir", ck,
                "--save-every", "4", "--batch", "2", "--seq", "16"])
    steps = sorted(d for d in os.listdir(ck) if d.startswith("step_"))
    assert "step_8" in steps


def test_serve_driver_runs(tmp_path):
    from repro.launch.serve import main as serve_main

    trace = str(tmp_path / "workload.serve-trace.jsonl")
    report = serve_main([
        "--arch", "gemma-2b", "--requests", "4", "--slots", "2",
        "--cache-len", "32", "--prefill-chunk", "8", "--max-new", "4",
        "--prompt-mean", "6", "--save-trace", trace,
        "--report", str(tmp_path / "report.json"),
    ])
    assert report["requests"] == 4 and report["tokens_out"] == 16
    # unshared prompts never copy-on-write: the copy graph never compiles
    assert report["jit_traces"] == {"decode": 1, "extend": 1, "copy": 0}
    assert report["paging"]["pages_in_use"] == 0
    # the saved trace replays to the identical deterministic tick metrics
    replay = serve_main([
        "--arch", "gemma-2b", "--trace", trace, "--slots", "2",
        "--cache-len", "32", "--prefill-chunk", "8",
    ])
    assert replay["latency_ticks"] == report["latency_ticks"]


def test_serve_driver_warm_restart(tmp_path):
    """--warm-restart persists the paged pool's prefix pages + wire
    fingerprints; a second identical run adopts them and dedups."""
    from repro.launch.serve import main as serve_main

    args = ["--arch", "gemma-2b", "--requests", "4", "--slots", "2",
            "--cache-len", "64", "--prefill-chunk", "8", "--page-size", "8",
            "--max-new", "4", "--prompt-mean", "10", "--rns-verify",
            "--seed", "3", "--warm-restart", str(tmp_path / "warm")]
    cold = serve_main(args)
    assert cold["warm_restart"]["restored"] is False  # nothing saved yet
    assert cold["warm_restart"]["pages_saved"] >= 1
    warm = serve_main(args)
    assert warm["warm_restart"]["restored"] is True
    assert warm["warm_restart"]["adopted"] >= 1
    assert warm["warm_restart"]["dropped"] == 0
    assert warm["paging"]["dedup_hits"] >= 1  # restart-surviving prefixes
    assert warm["rns"]["slots_failed"] == 0


@pytest.mark.parametrize("arch", ["mamba2-370m", "internvl2-26b"])
def test_serve_driver_single_shot_fallback(arch):
    """Gated families (ssm, vlm with its patch-prefix cache) still serve
    via the sequential fallback."""
    from repro.launch.serve import main as serve_main

    report = serve_main([
        "--arch", arch, "--requests", "2", "--max-new", "3",
        "--prompt-mean", "6",
    ])
    assert report["engine"] == "single-shot"
    assert report["requests"] == 2 and report["tokens_out"] == 6
    assert "jit_traces" not in report


def test_serve_driver_offline_mode(tmp_path):
    """--mode offline: warmed bucketed harness, retrace-free, report
    carries the saturation metrics and the overlap/bucket blocks."""
    from repro.launch.serve import main as serve_main

    report = serve_main([
        "--arch", "gemma-2b", "--mode", "offline", "--requests", "6",
        "--slots", "2", "--cache-len", "32", "--prefill-chunk", "8",
        "--buckets", "8,16,32", "--max-new", "4", "--prompt-mean", "6",
        "--report", str(tmp_path / "offline.json"),
    ])
    assert report["engine"] == "offline-harness"
    assert report["retrace_free"] is True
    assert report["requests"] == 6 and report["tokens_out"] == 24
    assert report["buckets"]["fallbacks"] == 0
    assert report["overlap"]["enabled"] and report["overlap"]["processed"] == 6
    assert report["ttft_s"]["n"] == 6
    assert (tmp_path / "offline.json").exists()


def test_serve_driver_loadgen_mode(tmp_path):
    """--mode loadgen: the QPS search runs to an SLO-pass attestation of
    a measured phase (generous SLO + low bracket keeps it fast)."""
    from repro.launch.serve import main as serve_main

    report = serve_main([
        "--arch", "gemma-2b", "--mode", "loadgen", "--slots", "2",
        "--cache-len", "32", "--prefill-chunk", "8",
        "--buckets", "8,16,32", "--max-new", "4", "--prompt-mean", "6",
        "--qps-lo", "20", "--qps-hi", "80", "--qps-iters", "1",
        "--phase-requests", "4",
        "--report", str(tmp_path / "loadgen.json"),
    ])
    assert report["mode"] == "loadgen"
    assert report["phases"]  # full transcript in the report
    if report["slo_pass"]:
        at = report["attestation"]
        assert at["slo_pass"] and at["retrace_free"]
        assert any(p["offered_qps"] == at["offered_qps"]
                   for p in report["phases"] if p["slo_pass"])
    assert (tmp_path / "loadgen.json").exists()


def test_serve_driver_mode_flag_validation():
    from repro.launch.serve import main as serve_main

    # the sim-only extras stay out of offline/loadgen
    with pytest.raises(SystemExit):
        serve_main(["--mode", "offline", "--page-size", "8",
                    "--rns-verify", "--warm-restart", "/tmp/nope"])
    with pytest.raises(SystemExit):
        serve_main(["--mode", "offline", "--rns-verify",
                    "--inject-wire-corrupt"])
    with pytest.raises(SystemExit):
        serve_main(["--mode", "loadgen", "--crypto-slots", "1"])
    with pytest.raises(SystemExit):
        serve_main(["--mode", "offline", "--buckets", "nope"])


def test_serve_driver_profiler_window(tmp_path):
    from repro.launch.serve import main as serve_main

    report = serve_main([
        "--arch", "gemma-2b", "--requests", "2", "--slots", "2",
        "--cache-len", "32", "--prefill-chunk", "8", "--max-new", "4",
        "--prompt-mean", "6", "--profile-start-step", "1",
        "--profile-steps", "2", "--profile-dir", str(tmp_path),
    ])
    prof = report["profile"]
    assert prof["captured_steps"] == 2
    assert prof["artifact"] and os.path.isdir(prof["artifact"])
    # the trace actually hit disk (an .xplane.pb under plugins/profile)
    hits = [f for _, _, fs in os.walk(prof["artifact"]) for f in fs
            if f.endswith(".xplane.pb")]
    assert hits, f"no xplane trace under {prof['artifact']}"


def test_train_driver_profiler_window(tmp_path, capsys):
    from repro.launch.train import main as train_main

    train_main(["--arch", "gemma-2b", "--steps", "4", "--batch", "2",
                "--seq", "16", "--profile-start-step", "1",
                "--profile-steps", "2", "--profile-dir", str(tmp_path)])
    assert "[profile] captured 2 step(s)" in capsys.readouterr().out
    hits = [f for _, _, fs in os.walk(str(tmp_path)) for f in fs
            if f.endswith(".xplane.pb")]
    assert hits, f"no xplane trace under {tmp_path}"


def test_serve_driver_rejects_duplicate_rids(tmp_path):
    from repro.launch.serve import main as serve_main

    trace = tmp_path / "dup.serve-trace.jsonl"
    trace.write_text(
        '{"rid": 3, "prompt": [1, 2], "max_new": 2}\n'
        '{"rid": 3, "prompt": [4, 5], "max_new": 2}\n'
    )
    with pytest.raises(ValueError, match="duplicate rids"):
        serve_main(["--arch", "gemma-2b", "--trace", str(trace),
                    "--cache-len", "32", "--prefill-chunk", "8"])


def test_dryrun_subprocess_small_mesh():
    """Lower+compile one cell with 8 fake devices in a subprocess —
    exercises the dryrun plumbing end-to-end without the 512-device cost."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import repro
from repro.launch.dryrun import lower_cell
from repro.dist.sharding import auto_mesh
mesh = auto_mesh((4, 2), ("data", "model"))
cfg, pa, lowered, meta = lower_cell("whisper-tiny", "train_4k", mesh,
                                    microbatches=4)
compiled = lowered.compile()
assert compiled.memory_analysis() is not None
print("SUBPROC_OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=420,
    )
    assert "SUBPROC_OK" in out.stdout, out.stderr[-2000:]


_CACHE_PROBE = """
import jax, jax.numpy as jnp
import repro
from repro.launch.compile_cache import enable_compile_cache
hits = []
jax.monitoring.register_event_listener(
    lambda event, **_: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None)
where = enable_compile_cache()
if {compile}:
    jax.jit(lambda x: jnp.sin(x) * 3 + x)(jnp.arange(8.0)).block_until_ready()
print("CACHE", where, len(hits))
"""


def _cache_probe(env, *, compile_fn):
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=compile_fn)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("CACHE ")]
    assert line, out.stderr[-2000:]
    _, where, hits = line[-1].split()
    return where, int(hits)


def test_compile_cache_placement(tmp_path):
    """``enable_compile_cache``: an outside ``JAX_COMPILATION_CACHE_DIR`` is
    where entries go, and a second process hits them; without the variable
    the cache is ``<checkout>/.jax_cache``, the same path in every process."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    checkout = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))
    default = os.path.join(checkout, ".jax_cache")
    assert _cache_probe(env, compile_fn=False)[0] == default

    outside = dict(env, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    first = _cache_probe(outside, compile_fn=True)
    assert first == (str(tmp_path / "cc"), 0)
    assert os.listdir(tmp_path / "cc")
    assert _cache_probe(outside, compile_fn=True)[1] >= 1


def test_chip_smoke_refuses_without_tpu():
    """chip_smoke.py never falls back to the CPU: with no TPU it exits
    non-zero, says so, and prints no result line."""
    script = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, env=env,
        timeout=300,
    )
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout
