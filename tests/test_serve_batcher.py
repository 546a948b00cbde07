"""Continuous-batching engine invariants (DESIGN.md §12).

The tier-1 contract of the serve subsystem:

* slot ISOLATION — a request packed against staggered co-resident
  traffic, including one admitted mid-decode, yields the tokens of a plain
  batch-1 greedy loop over the model and its K/V rows within the stated
  float32 tolerance (``conftest.reference``);
* slot REUSE — retirement returns rows to the pool and later admissions
  recycle them;
* NO RETRACE — the engine's jitted graphs each compile exactly once no
  matter how occupancy churns (asserted via jit cache stats);
* RNS integrity — prompt-page fingerprints verify at retirement, and an
  injected wire-buffer corruption is detected and repaired in place
  through ``dist.fault.repair_packed``.
"""
import dataclasses

import numpy as np
import pytest

import jax

import repro  # noqa: F401
from conftest import (
    CACHE_LEN,
    CHUNK,
    PAGE,
    check_against_reference,
    make_engine,
    run_with_row_snapshots,
    snapshot_rows,
)
from repro.configs import get_config
from repro.dist.sharding import auto_mesh
from repro.models import init_params
from repro.serve.batcher import ContinuousBatcher
from repro.serve.scheduler import Request, SlotScheduler


def _requests(cfg, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda rid, plen, max_new: Request(
        rid=rid, prompt=[int(t) for t in rng.integers(1, cfg.vocab, plen)],
        max_new=max_new,
    )
    # prompt lengths straddle the prefill chunk (3 < 8 < 11) so admission
    # exercises both the single-chunk and the multi-chunk path
    return [mk(0, 5, 8), mk(1, 11, 7), mk(2, 3, 9)]


_engine = make_engine  # shared factory (tests/conftest.py)


def _run_mixed(cfg, params):
    """Staggered admissions: r0 streams alone, r1 joins mid-decode, then
    r2 — with all three overlapping before any retirement.  Returns the
    engine, the requests and their K/V rows at retirement."""
    eng = _engine(cfg, params)
    rows = snapshot_rows(eng)
    reqs = _requests(cfg)
    eng.submit(reqs[0])
    eng.try_admit()
    eng.step(), eng.step()
    eng.submit(reqs[1])
    eng.try_admit()
    eng.step()
    eng.submit(reqs[2])
    eng.try_admit()
    assert len(eng.sched.decoding_slots()) == 3  # genuine 3-way overlap
    while eng.sched.busy:
        eng.try_admit()
        eng.step()
    return eng, reqs, rows


def test_mid_stream_admission_bitwise_vs_solo(cfg, params):
    """Each request of the staggered run matches the plain reference:
    the same tokens, and the whole K/V trajectory (not just the argmaxes)
    within the float32 tolerance."""
    eng, reqs, rows = _run_mixed(cfg, params)
    mixed = {r.rid: r for r in eng.sched.completed}
    assert sorted(mixed) == [0, 1, 2]
    assert len({r.slot_index for r in reqs}) == 3  # three distinct rows
    for r in reqs:
        assert len(mixed[r.rid].out) == r.max_new
        check_against_reference(cfg, params, r, rows[r.rid])


def test_prefill_chunk_size_is_bitwise_invisible(cfg, params):
    """Chunks of 4 and of 16 tokens (different extend graphs) each give
    the reference's tokens and K/V rows."""
    outs = []
    for chunk in (4, 16):
        eng = _engine(cfg, params, prefill_chunk=chunk)
        done, rows = run_with_row_snapshots(eng, _requests(cfg))
        for r in done.values():
            check_against_reference(cfg, params, r, rows[r.rid])
        outs.append({rid: r.out for rid, r in done.items()})
    assert outs[0] == outs[1]


def test_slot_reuse_after_retirement(cfg, params):
    eng = _engine(cfg, params, n_slots=2)
    rng = np.random.default_rng(3)
    for i in range(5):
        eng.submit(Request(
            rid=i, prompt=[int(t) for t in rng.integers(1, cfg.vocab, 4)],
            max_new=3 + i % 3,
        ))
    done = eng.run_to_completion()
    assert sorted(r.rid for r in done) == list(range(5))
    assert all(len(r.out) == r.max_new for r in done)
    by_slot = {}
    for r in done:
        by_slot.setdefault(r.slot_index, []).append(r.rid)
    assert set(by_slot) <= {0, 1}               # never more rows than slots
    assert max(len(v) for v in by_slot.values()) >= 2  # rows were recycled


def test_no_retrace_across_churn(cfg, params):
    eng, _, _ = _run_mixed(cfg, params)
    sizes = eng.jit_cache_sizes()
    # unshared prompts never copy-on-write: the copy graph never compiles
    assert sizes == {"decode": 1, "extend": 1, "copy": 0}, sizes


def test_eos_retires_early(cfg, params):
    eng = _engine(cfg, params)
    probe = Request(rid=0, prompt=[1, 2, 3], max_new=6)
    eng.submit(probe)
    first = eng.run_to_completion()[0].out[0]
    eng2 = _engine(cfg, params)
    eng2.submit(Request(rid=1, prompt=[1, 2, 3], max_new=6, eos=first))
    done = eng2.run_to_completion()
    assert done[0].out == [first]  # instant EOS: one token, slot freed


def test_rns_verify_and_injected_corruption_repair(cfg, params):
    eng = _engine(cfg, params, n_slots=2, rns_verify=True)
    for r in _requests(cfg):
        eng.submit(r)
    # one-token budget: retires inside admission, must still be verified
    eng.submit(Request(rid=9, prompt=[1, 2, 3], max_new=1))
    done = eng.run_to_completion()
    # every retirement verified its prompt-page fingerprints bitwise
    assert eng.verify_log == {r.rid: True for r in done}
    assert 9 in eng.verify_log
    # the full prompt pages stay retained (shareable) with their codewords
    keys = sorted(eng._wire)
    assert keys and set(keys) == set(eng.sched.alloc.retained)
    assert all(eng.wire_ok(k) for k in keys)
    # inject a single-channel wire corruption: detected, located,
    # corrected in place, and the repaired buffer re-verifies against the
    # (recomputable) fingerprint encoding
    pid = keys[0]
    stored = eng._wire[pid].residues.copy()
    eng.corrupt_wire(pid, channel=1, delta=3)
    assert not eng.wire_ok(pid)
    report = eng.repair_wire(pid)
    assert report == {"repaired": 1, "unrecoverable": 0}
    assert eng.wire_ok(pid)
    np.testing.assert_array_equal(np.asarray(eng._wire[pid].residues),
                                  np.asarray(stored))
    assert eng.wire.matches(pid, eng._page_codewords([pid])[0])
    assert eng.jit_cache_sizes()["fingerprint"] == 1


def test_fingerprint_stays_valid_after_retirement(cfg, params):
    """A retired request's retained prompt page must keep verifying while
    other slots decode on (idle rows write only the parking page), until
    the page is actually reused."""
    eng = _engine(cfg, params, n_slots=2, rns_verify=True)
    short = Request(rid=0, prompt=list(range(1, PAGE + 1)), max_new=2)
    long = Request(rid=1, prompt=[40, 41, 42], max_new=8)
    eng.submit(short), eng.submit(long)
    eng.try_admit()
    (pid,) = [p for lp, p in eng.sched.slot_pages(short.slot_index)
              if lp == 0]
    while short.t_done is None:
        eng.step()
    assert eng.verify_log[0] is True
    assert eng.sched.alloc.is_retained(pid)  # rid 0's page outlives it
    for _ in range(3):  # rid 0's slot sits FREE while rid 1 decodes
        eng.step()
    assert long.t_done is None
    assert eng.wire.matches(pid, eng._page_codewords([pid])[0])


def test_drain_completed_releases_state(cfg, params):
    eng = _engine(cfg, params, n_slots=2, rns_verify=True)
    for r in _requests(cfg):
        eng.submit(r)
    eng.run_to_completion()
    done = eng.drain_completed()
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert eng.sched.completed == [] and eng.verify_log == {}
    # freed pages dropped their codewords at retirement; only the
    # retained (shareable) prompt pages keep theirs
    assert set(eng._wire) == set(eng.sched.alloc.retained)
    assert eng.sched.alloc.in_use == 0


def test_chunk_must_divide_cache_len(cfg, params):
    with pytest.raises(ValueError, match="must divide"):
        _engine(cfg, params, cache_len=30, prefill_chunk=8)


def test_duplicate_rid_rejected_under_rns_verify(cfg, params):
    """Verify state is keyed on rid; a collision must fail loudly at
    submit — before any slot is bound — instead of silently cross-wiring
    fingerprints or wedging an admitted slot."""
    eng = _engine(cfg, params, n_slots=2, rns_verify=True)
    eng.submit(Request(rid=7, prompt=[1, 2, 3], max_new=4))
    with pytest.raises(ValueError, match="already holds verify state"):
        eng.submit(Request(rid=7, prompt=[4, 5, 6], max_new=4))
    done = eng.run_to_completion()  # the engine is NOT wedged
    assert [r.rid for r in done] == [7]
    # after draining, the rid is reusable
    eng.drain_completed()
    eng.submit(Request(rid=7, prompt=[1, 2], max_new=2))
    assert len(eng.run_to_completion()) == 1


def test_unsupported_families_are_gated(params):
    ssm = get_config("mamba2-370m").smoke()
    with pytest.raises(NotImplementedError, match="linear-KV"):
        ContinuousBatcher(ssm, {}, n_slots=1, cache_len=16)
    dense = get_config("gemma-2b").smoke()
    quant = dataclasses.replace(dense, kv_quant=True)
    with pytest.raises(NotImplementedError, match="int8"):
        ContinuousBatcher(quant, {}, n_slots=1, cache_len=16)


def test_oversized_request_fails_at_submit(cfg, params):
    sch = SlotScheduler(n_slots=1, cache_len=8)
    with pytest.raises(ValueError, match="exceeds"):
        sch.submit(Request(rid=0, prompt=[1] * 6, max_new=4))


def test_windowed_arch_lowers_to_masked_cache(params):
    """gemma3's grouped ring cache lowers to the linear masked layout so
    every slot row pages; the engine still streams correctly."""
    cfg3 = get_config("gemma3-1b").smoke()
    assert cfg3.window and cfg3.window_cache
    p3 = init_params(cfg3, jax.random.key(2))
    eng = ContinuousBatcher(cfg3, p3, n_slots=2, cache_len=CACHE_LEN,
                            prefill_chunk=CHUNK)
    assert not eng.cfg.window_cache
    eng.submit(Request(rid=0, prompt=[4, 5, 6, 7], max_new=4))
    done = eng.run_to_completion()
    assert len(done[0].out) == 4


def test_sharded_cache_placement(cfg, params):
    """mesh= places every pool leaf on its cache_specs sharding and the
    weights on the mesh too (trivially replicated on one device), and
    the graphs hand the pool back in that placement."""
    mesh = auto_mesh((1,), ("data",))
    eng = _engine(cfg, params, mesh=mesh)
    for name in ("k", "v"):
        assert eng.cache[name].sharding.spec == eng.cache_pspecs[name]
    assert all(leaf.sharding.mesh == mesh
               for leaf in jax.tree_util.tree_leaves(eng.params))
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new=3))
    assert len(eng.run_to_completion()[0].out) == 3
    assert eng.cache["k"].sharding.spec == eng.cache_pspecs["k"]
