"""Checkpointer robustness (DESIGN.md §14).

The tier-1 contract of the async RRNS-coded checkpointer:

* policy grammar — overlapping step/time intervals, most-specific first;
* error propagation — a failed background save surfaces on the next
  ``wait()`` / ``close()`` / ``join()``, never vanishes with its thread;
* atomicity — a committed ``step_<N>`` is all-or-nothing; SIGKILL mid-save
  leaves only a ``.tmp`` remnant that the next run sweeps;
* repair-on-restore — one corrupted RRNS channel per buffer is located
  and rebuilt in stride (reported); multi-channel damage is REFUSED and
  restore falls back to the next restorable step;
* kill-and-resume — a trainer SIGKILLed during an async save resumes
  from the survivor checkpoint bitwise-equal to an uninterrupted run;
* elastic restore — a ZeRO-1 state saved under one mesh device_puts onto
  a different mesh shape on load (checkpoints hold full host arrays);
* warm serve restart — the paged pool's prefix pages and their wire
  fingerprints persist and revalidate across an engine restart;
* legacy scanner — ``fault.scan_restorable`` skips torn / corrupt /
  foreign directories and lands on the newest verified legacy step.
"""
import json
import os
import signal
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro  # noqa: F401  (x64)
from repro.dist import fault
from repro.train import checkpoint
from repro.train import checkpointer as cp

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

TRAIN_ARGS = ["--arch", "gemma-2b", "--steps", "8", "--batch", "2",
              "--seq", "16", "--save-every", "4"]


# ------------------------------------------------------------ save policy
def test_parse_policy_overlapping_intervals():
    pol = cp.parse_policy("2@10,5,30s")
    due = [s for s in range(1, 21) if pol.step_due(s)]
    assert due == [2, 4, 6, 8, 10, 15, 20]  # dense early, sparse after
    assert pol.every_seconds == 30.0
    assert not pol.step_due(0)  # step 0 is the init state, never due


def test_policy_time_due_is_wall_clock_only():
    pol = cp.parse_policy("1m")
    assert not any(pol.step_due(s) for s in range(1, 200))
    assert pol.time_due(now=100.0, last=30.0)
    assert not pol.time_due(now=100.0, last=50.0)


@pytest.mark.parametrize("bad", ["0", "-1", "2@", "x", "3s,4s", "5,7"])
def test_parse_policy_rejects_malformed(bad):
    with pytest.raises(ValueError):
        cp.parse_policy(bad)


# ----------------------------------------------- lossless RRNS round trip
def test_write_read_round_trip_mixed_dtypes(tmp_path):
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": {"step": np.array(7, dtype=np.int32),   # 0-d stays 0-d
                  "h": jnp.full((3,), 1.5, jnp.bfloat16)},
            "odd": np.frombuffer(b"xyz", dtype=np.uint8)}  # 3 bytes: padded
    cp.write_step_dir(str(tmp_path), 5, tree, extra={"opt_step": 5})
    restored, step, extra, rep = cp.restore(str(tmp_path))
    assert (step, extra) == (5, {"opt_step": 5})
    assert rep["repaired_leaves"] == 0 and rep["steps_skipped"] == 0
    assert restored["b"]["step"].shape == ()
    np.testing.assert_array_equal(restored["a"], tree["a"])
    np.testing.assert_array_equal(restored["b"]["h"],
                                  np.asarray(tree["b"]["h"]))
    np.testing.assert_array_equal(restored["odd"], tree["odd"])


def test_single_channel_corruption_repaired_on_restore(tmp_path):
    tree = {"w": np.arange(8, dtype=np.float32)}
    cp.write_step_dir(str(tmp_path), 1, tree)
    cp.inject_channel_corruption(str(tmp_path / "step_1"), leaf=0,
                                 channels=(2,), index=3)
    restored, step, _, rep = cp.restore(str(tmp_path))
    assert step == 1
    assert rep["repaired_leaves"] == 1 and rep["repaired_elements"] == 1
    np.testing.assert_array_equal(restored["w"], tree["w"])  # exact rebuild


def test_two_channel_damage_refused_with_fallback(tmp_path):
    cp.write_step_dir(str(tmp_path), 1, {"w": np.ones(4, np.float32)})
    cp.write_step_dir(str(tmp_path), 2, {"w": np.full(4, 2.0, np.float32)})
    # two BASE channels of one element: beyond single-channel repair
    cp.inject_channel_corruption(str(tmp_path / "step_2"), channels=(0, 1))
    with pytest.raises(cp.CheckpointCorrupt):
        cp.restore(str(tmp_path), step=2)  # explicit step: refuse loudly
    restored, step, _, rep = cp.restore(str(tmp_path))
    assert step == 1 and rep["steps_skipped"] == 1  # fell back, counted
    np.testing.assert_array_equal(restored["w"], np.ones(4))


def test_truncated_wire_file_falls_back(tmp_path):
    cp.write_step_dir(str(tmp_path), 1, {"w": np.ones(4)})
    cp.write_step_dir(str(tmp_path), 2, {"w": np.zeros(4)})
    f = tmp_path / "step_2" / "0.rns.npy"
    f.write_bytes(f.read_bytes()[:10])
    restored, step, _, rep = cp.restore(str(tmp_path))
    assert step == 1 and rep["steps_skipped"] == 1
    with pytest.raises(cp.CheckpointCorrupt):
        cp.read_step_dir(str(tmp_path / "step_2"))


def test_discover_ignores_tmp_and_foreign_entries(tmp_path):
    assert cp.discover_latest(str(tmp_path)) is None
    (tmp_path / "step_4.tmp").mkdir()
    (tmp_path / "step_abc").mkdir()
    (tmp_path / "notes.txt").write_text("x")
    assert cp.discover_steps(str(tmp_path)) == []
    cp.write_step_dir(str(tmp_path), 10, {"a": np.zeros(1)})
    cp.write_step_dir(str(tmp_path), 2, {"a": np.zeros(1)})
    assert cp.discover_steps(str(tmp_path)) == [2, 10]
    assert cp.discover_latest(str(tmp_path)) == 10


# ----------------------------------------------------- Checkpointer class
def test_checkpointer_policy_gc_and_tmp_sweep(tmp_path):
    (tmp_path / "step_7.tmp").mkdir()  # torn remnant of a "crash"
    tree = {"a": np.arange(3, dtype=np.float32)}
    with cp.Checkpointer(str(tmp_path), "2@4,3", keep=2) as saver:
        assert not (tmp_path / "step_7.tmp").exists()  # swept at init
        enq = [s for s in range(1, 10) if saver.maybe_save(s, tree)]
    assert enq == [2, 4, 6, 9]  # bounded interval first, then every 3
    assert cp.discover_steps(str(tmp_path)) == [6, 9]  # GC kept newest 2
    restored, step, _, _ = cp.restore(str(tmp_path))
    assert step == 9
    np.testing.assert_array_equal(restored["a"], tree["a"])


def test_checkpointer_worker_error_surfaces_on_wait(tmp_path, monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("disk full")

    monkeypatch.setattr(cp, "write_step_dir", boom)
    saver = cp.Checkpointer(str(tmp_path), "1")
    saver.save(1, {"a": np.zeros(2)})
    with pytest.raises(RuntimeError, match="disk full"):
        saver.wait()
    saver.close()  # error already consumed: close is clean


def test_checkpointer_worker_error_surfaces_on_close(tmp_path, monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("disk full")

    monkeypatch.setattr(cp, "write_step_dir", boom)
    saver = cp.Checkpointer(str(tmp_path), "1")
    saver.save(1, {"a": np.zeros(2)})
    with pytest.raises(RuntimeError, match="disk full"):
        saver.close()


# ------------------------------------------- legacy checkpoint satellites
def test_save_commits_atomically_no_tmp_left(tmp_path):
    path = checkpoint.save(str(tmp_path), 2, {"a": np.arange(4)})
    assert os.path.basename(path) == "step_2"
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


def test_save_async_error_reraised_on_join(tmp_path):
    target = tmp_path / "ck"
    target.write_text("a FILE where the ckpt dir should be")
    handle = checkpoint.save_async(str(target), 1, {"a": np.zeros(2)})
    with pytest.raises(OSError):
        handle.join()


def test_save_async_same_step_guard(tmp_path, monkeypatch):
    release, started = threading.Event(), threading.Event()
    real_save = checkpoint.save

    def slow_save(*a, **k):
        started.set()
        assert release.wait(10)
        return real_save(*a, **k)

    monkeypatch.setattr(checkpoint, "save", slow_save)
    handle = checkpoint.save_async(str(tmp_path), 3, {"a": np.zeros(2)})
    assert started.wait(10)
    with pytest.raises(RuntimeError, match="in flight"):
        checkpoint.save_async(str(tmp_path), 3, {"a": np.zeros(2)})
    release.set()
    assert handle.join() == str(tmp_path / "step_3")
    # the guard clears with the thread: the same step saves again fine
    checkpoint.save_async(str(tmp_path), 3, {"a": np.zeros(2)}).join()


def test_scan_restorable_edge_cases(tmp_path):
    # empty / missing dirs and non-checkpoint entries: None, no crash
    assert fault.scan_restorable(str(tmp_path)) is None
    assert fault.scan_restorable(str(tmp_path / "nope")) is None
    (tmp_path / "notes.txt").write_text("x")
    (tmp_path / "step_xyz").mkdir()
    assert fault.find_restorable(str(tmp_path)) is None

    checkpoint.save(str(tmp_path), 1, {"a": np.arange(3)})
    # newest step loses a tensor file -> scan falls back one step
    checkpoint.save(str(tmp_path), 2, {"a": np.arange(4)})
    os.remove(tmp_path / "step_2" / "0.npy")
    path, manifest, flat = fault.scan_restorable(str(tmp_path))
    assert path.endswith("step_1") and manifest["step"] == 1
    np.testing.assert_array_equal(flat["a"], np.arange(3))

    # torn save (no manifest with the fingerprints) -> skipped
    checkpoint.save(str(tmp_path), 3, {"a": np.arange(5)})
    os.remove(tmp_path / "step_3" / "manifest.json")
    assert fault.find_restorable(str(tmp_path)).endswith("step_1")

    # bit rot under an intact manifest -> fingerprint mismatch, skipped
    checkpoint.save(str(tmp_path), 4, {"a": np.arange(6)})
    rotten = np.load(tmp_path / "step_4" / "0.npy")
    rotten[0] ^= 1
    np.save(tmp_path / "step_4" / "0.npy", rotten)
    assert fault.find_restorable(str(tmp_path)).endswith("step_1")

    # a NEW-format (rrns-v1) dir is skipped cleanly by the legacy scanner
    cp.write_step_dir(str(tmp_path), 9, {"a": np.arange(7)})
    assert fault.find_restorable(str(tmp_path)).endswith("step_1")


# ------------------------------------------------- kill-and-resume chaos
def _leaf_shas(step_dir):
    with open(os.path.join(step_dir, "manifest.json")) as f:
        return [leaf["sha"] for leaf in json.load(f)["leaves"]]


def test_sigkill_mid_save_then_resume_bitwise_equal(tmp_path, capsys):
    """SIGKILL lands inside the background writer after the first leaf
    file of step_8: the torn .tmp never commits, step_4 survives, and the
    resumed trainer re-runs 4..8 to a checkpoint bitwise-identical to an
    uninterrupted run's."""
    from repro.launch.train import main as train_main

    ref, ck = str(tmp_path / "ref"), str(tmp_path / "ck")
    train_main(TRAIN_ARGS + ["--ckpt-dir", ref])  # uninterrupted baseline

    env = dict(os.environ, PYTHONPATH=SRC)
    env[cp.CRASH_STEP_ENV] = "8"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", *TRAIN_ARGS,
         "--ckpt-dir", ck],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == -signal.SIGKILL, out.stderr[-2000:]
    names = os.listdir(ck)
    assert "step_8.tmp" in names and "step_8" not in names  # torn, by design
    assert "step_4" in names  # the committed survivor

    capsys.readouterr()
    train_main(TRAIN_ARGS + ["--ckpt-dir", ck])  # resume 4 -> 8
    log = capsys.readouterr().out
    assert "[resume] restored step 4" in log
    assert not os.path.exists(os.path.join(ck, "step_8.tmp"))  # swept
    assert _leaf_shas(os.path.join(ck, "step_8")) == \
        _leaf_shas(os.path.join(ref, "step_8"))  # bitwise-equal resume


def test_resume_repairs_single_channel_and_refuses_two(tmp_path, capsys):
    """The driver's --inject-ckpt-corrupt path: 1 channel is repaired in
    stride and logged; 2 base channels force fallback to the prior step."""
    from repro.launch.train import main as train_main

    ck = str(tmp_path / "ck")
    train_main(TRAIN_ARGS + ["--ckpt-dir", ck])
    capsys.readouterr()
    train_main(TRAIN_ARGS + ["--ckpt-dir", ck, "--inject-ckpt-corrupt", "1"])
    log = capsys.readouterr().out
    assert "repaired_leaves=1" in log and "restored step 8" in log
    train_main(TRAIN_ARGS + ["--ckpt-dir", ck, "--inject-ckpt-corrupt", "2"])
    log = capsys.readouterr().out
    assert "restored step 4" in log and "steps_skipped=1" in log


# ------------------------------------------------------- elastic restore
def test_elastic_restore_reshards_zero1_state():
    """Save a ZeRO-1 train state under a (4,2) mesh, restore it under a
    (2,4) mesh: values identical, shardings are the NEW mesh's.  One
    subprocess so the 8-device XLA flag never pollutes this process."""
    code = """
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
import repro
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.models import init_params
from repro.train.optimizer import adamw_init
from repro.train import checkpointer as cp
from repro.dist.sharding import named_shardings, opt_state_specs, param_specs
from repro.dist.sharding import auto_mesh

cfg = get_config("gemma-2b").smoke()
params = init_params(cfg, jax.random.key(0))
abs_p = jax.tree_util.tree_map(
    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)

def shardings(mesh):
    pspecs = param_specs(abs_p, mesh, n_experts=cfg.n_experts)
    z = opt_state_specs(abs_p, pspecs, mesh, zero1=True)
    return named_shardings(
        {"params": pspecs, "opt": {"m": z, "v": z, "step": P()}}, mesh)

meshA = auto_mesh((4, 2), ("data", "model"))
shA = shardings(meshA)
tree = jax.device_put({"params": params, "opt": adamw_init(params)}, shA)
ckpt = tempfile.mkdtemp()
cp.write_step_dir(ckpt, 7, tree)

meshB = auto_mesh((2, 4), ("data", "model"))
shB = shardings(meshB)
abs_tree = jax.tree_util.tree_map(
    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
out, step, extra, rep = cp.restore(ckpt, abs_tree, shB)
assert step == 7 and rep["repaired_leaves"] == 0
flat_o = jax.tree_util.tree_leaves(out)
flat_s = jax.tree_util.tree_leaves(shB, is_leaf=lambda x: hasattr(x, "spec"))
assert len(flat_o) == len(flat_s)
assert all(o.sharding == s for o, s in zip(flat_o, flat_s))
jax.tree_util.tree_map(
    lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
    tree, out)
print("SUBPROC_OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=420)
    assert "SUBPROC_OK" in out.stdout, out.stderr[-2000:]


# ---------------------------------------------------- warm serve restart
@pytest.fixture(scope="module")
def scfg():
    from repro.configs import get_config

    return get_config("gemma-2b").smoke()


@pytest.fixture(scope="module")
def sparams(scfg):
    from repro.models import init_params

    return init_params(scfg, jax.random.key(0))


def _serve_engine(scfg, sparams, **kw):
    from repro.serve.batcher import ContinuousBatcher

    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", 32)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("page_size", 8)
    kw.setdefault("rns_verify", True)
    return ContinuousBatcher(scfg, sparams, **kw)


def _shared_prefix_reqs(scfg, seed=5):
    from repro.serve.scheduler import Request

    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, scfg.vocab, 8)]
    return prefix, [Request(rid=i, prompt=prefix + [30 + i], max_new=3)
                    for i in range(2)]


def test_warm_restart_adopts_pages_bitwise(tmp_path, scfg, sparams):
    from repro.serve.scheduler import Request

    prefix, reqs = _shared_prefix_reqs(scfg)
    eng = _serve_engine(scfg, sparams)
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    saved = eng.save_warm_state(str(tmp_path))
    assert saved["pages_saved"] >= 1  # the retained shared-prefix chain

    fresh = _serve_engine(scfg, sparams)
    rep = fresh.load_warm_state(str(tmp_path))
    assert rep["adopted"] == saved["pages_saved"]
    assert rep["dropped"] == 0 and rep["repaired_pages"] == 0

    # the adopted pages dedup a new same-prefix request after the restart
    fresh.submit(Request(rid="new", prompt=prefix + [9], max_new=3))
    done = fresh.run_to_completion()
    assert fresh.page_stats()["dedup_hits"] >= 1
    assert fresh.verify_log["new"] is True  # retirement re-verify passes

    cold = _serve_engine(scfg, sparams)  # bitwise vs a cold engine
    cold.submit(Request(rid="new", prompt=prefix + [9], max_new=3))
    cdone = cold.run_to_completion()
    assert [r.out for r in done] == [r.out for r in cdone]


def test_warm_restart_repairs_corrupted_state_file(tmp_path, scfg, sparams):
    _, reqs = _shared_prefix_reqs(scfg)
    eng = _serve_engine(scfg, sparams)
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    saved = eng.save_warm_state(str(tmp_path))
    # one RRNS channel of one saved leaf rots on disk
    cp.inject_channel_corruption(str(tmp_path / "step_0"), leaf=0,
                                 channels=(2,))
    fresh = _serve_engine(scfg, sparams)
    rep = fresh.load_warm_state(str(tmp_path))
    assert rep["ckpt_repaired_leaves"] == 1  # fixed at the checkpoint layer
    assert rep["adopted"] == saved["pages_saved"] and rep["dropped"] == 0


def test_warm_restart_drops_unrepairable_page(tmp_path, scfg, sparams):
    """A stored page codeword rotten in TWO base channels round-trips
    losslessly through the checkpoint, fails revalidation on load, and the
    page (with any descendants) is dropped instead of trusted."""
    _, reqs = _shared_prefix_reqs(scfg)
    eng = _serve_engine(scfg, sparams)
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    retained = list(eng.sched.alloc.retained)
    assert retained
    eng.corrupt_wire(retained[0], channel=0, delta=3)
    eng.corrupt_wire(retained[0], channel=1, delta=3)
    saved = eng.save_warm_state(str(tmp_path))
    fresh = _serve_engine(scfg, sparams)
    rep = fresh.load_warm_state(str(tmp_path))
    assert rep["dropped"] >= 1
    assert rep["adopted"] == saved["pages_saved"] - rep["dropped"]
