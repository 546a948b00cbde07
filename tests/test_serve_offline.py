"""Saturation harness invariants (DESIGN.md §16).

The tier-1 contract of the offline measurement layer:

* bucketed prefill is INVISIBLE — a prompt padded to its bucket and
  prefilled in ONE extend call produces the same tokens as the chunked
  loop, for LLM and mixed LLM+crypto traffic, and both hold to the plain
  reference (``conftest.reference``): its tokens, and its KV rows within
  the stated float32 tolerance;
* warmup pre-compiles every (bucket, family) graph and the timed run adds
  ZERO retraces (the ``extend`` cache counts exactly the warmed widths);
* the completion pump preserves FIFO under a slow callback, applies
  bounded-queue backpressure, and propagates the FIRST callback error
  from put()/flush()/close() — never a silent hang;
* the replica set dispatches a shared admission queue to the least-loaded
  replica and completes everything exactly once.
"""
import threading
import time

import numpy as np
import pytest

import repro  # noqa: F401
from conftest import (
    CACHE_LEN,
    CHUNK,
    check_against_reference,
    make_engine,
    run_with_row_snapshots,
)
from repro.serve.offline import (
    CompletionPump,
    OfflineInference,
    ReplicaSet,
    pow2_buckets,
    replica_meshes,
    sample_stats,
)
from repro.serve.scheduler import Request

BUCKETS = (8, 16, 32)


def _requests(cfg, seed=0, n=4):
    rng = np.random.default_rng(seed)
    # lengths straddle the buckets: 5 -> 8, 11 -> 16, 3 -> 8, 17+ -> 32
    plens = [5, 11, 3, 17, 23, 7][:n]
    return [
        Request(rid=i,
                prompt=[int(t) for t in rng.integers(1, cfg.vocab, p)],
                max_new=6, eos=-1)
        for i, p in enumerate(plens)
    ]


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 4)
    return make_engine(cfg, params, **kw)


# -- bucketed prefill bitwise identity ------------------------------------


def test_bucketed_prefill_bitwise_identity(cfg, params):
    """Same trace through the chunk loop and through single-call bucketed
    prefill: the same tokens, and on both the reference's tokens and its
    full KV trajectory within the float32 tolerance — the pad region
    beyond plen-1 is causally invisible (logit_index reads the last real
    position; pads land in the scratch page)."""
    chunked = _engine(cfg, params)
    chunk_done, chunk_rows = run_with_row_snapshots(chunked, _requests(cfg))

    bucketed = _engine(cfg, params, prefill_buckets=BUCKETS)
    reqs_b = _requests(cfg)
    buck_done, buck_rows = run_with_row_snapshots(bucketed, reqs_b)

    assert sorted(buck_done) == sorted(chunk_done)
    for rid, rb in buck_done.items():
        rc = chunk_done[rid]
        assert rb.out == rc.out
        check_against_reference(cfg, params, rb, buck_rows[rid])
        check_against_reference(cfg, params, rc, chunk_rows[rid])
    st = bucketed.bucket_stats()
    assert sum(st["hits"].values()) == len(reqs_b)  # every prompt bucketed
    assert st["fallbacks"] == 0
    assert st["pad_tokens"] > 0  # the identity was demonstrated ON pads


def test_bucketed_identity_with_crypto_family(cfg, params):
    """Mixed LLM + crypto traffic: bucketing the LLM lane must not
    disturb either lane's results (one shared engine step interleaves
    decode ticks and ladder chunks)."""
    from repro.serve.crypto import CryptoContext, CryptoRequest

    ctx = CryptoContext(n_limbs=8, exp_bits=16)

    def crypto_reqs(rid0):
        return [
            CryptoRequest(rid=rid0, op="modexp", a=12345, b=777, n=99991),
            CryptoRequest(rid=rid0 + 1, op="modmul", a=4321, b=8765,
                          n=99991),
        ]

    results = []
    for buckets in (None, BUCKETS):
        eng = _engine(cfg, params, prefill_buckets=buckets,
                      crypto_slots=2, crypto_ctx=ctx)
        for r in _requests(cfg, n=3):
            eng.submit(r)
        for r in crypto_reqs(100):
            eng.submit(r)
        eng.run_to_completion()
        llm = {r.rid: list(r.out) for r in eng.sched.completed}
        crypto = {r.rid: r.result for r in eng.crypto.completed}
        results.append((llm, crypto))
    assert results[0] == results[1]
    assert results[0][1][100] == pow(12345, 777, 99991)
    assert results[0][1][101] == (4321 * 8765) % 99991


def test_bucket_stats_count_fallback_traffic(cfg, params):
    """Over-bucket prompts fall back to the chunk loop; their chunk-grid
    pads AND real tokens must still land in the pad-overhead accounting.
    (Regression: fallback tokens used to vanish from both terms, so
    ``pad_overhead`` understated pad cost and overstated the bucketed
    share of traffic.)"""
    rng = np.random.default_rng(5)
    mk = lambda rid, plen: Request(
        rid=rid, prompt=[int(t) for t in rng.integers(1, cfg.vocab, plen)],
        max_new=2)
    eng = _engine(cfg, params, prefill_buckets=(8,))
    eng.submit(mk(0, 5))   # bucketed: 3 pads / 5 real
    eng.submit(mk(1, 20))  # fallback: ceil(20/8)*8 - 20 = 4 pads / 20 real
    eng.run_to_completion()
    st = eng.bucket_stats()
    assert st["fallbacks"] == 1 and st["hits"]["8"] == 1
    assert st["pad_tokens"] == 3 + 4
    assert st["real_tokens"] == 5 + 20
    assert st["pad_overhead"] == pytest.approx(7 / 25)
    # same contract at pages of 16 ("real" = tokens the extend computed,
    # so the fallback's chunk-grid pads count there too)
    pgd = _engine(cfg, params, page_size=16, prefill_buckets=(8,))
    pgd.submit(mk(2, 20))
    pgd.run_to_completion()
    st = pgd.bucket_stats()
    assert st["fallbacks"] == 1
    assert st["pad_tokens"] == 4 and st["real_tokens"] == 20


def test_bucket_validation(cfg, params):
    # the ladder reaches the scheduler so admission reserves by the same
    # bucketed-vs-chunk rule the engine dispatches by (padded write
    # barrier)
    eng = _engine(cfg, params, page_size=8, prefill_buckets=BUCKETS)
    assert eng.sched.prefill_buckets == BUCKETS
    with pytest.raises(ValueError, match="out of range"):
        _engine(cfg, params, prefill_buckets=(0, 8))
    with pytest.raises(ValueError, match="out of range"):
        _engine(cfg, params, prefill_buckets=(8, CACHE_LEN + 1))
    with pytest.raises(ValueError, match=">= 1 bucket"):
        _engine(cfg, params, prefill_buckets=())


def test_pow2_buckets_ladder():
    assert pow2_buckets(128) == (8, 16, 32, 64, 128)
    assert pow2_buckets(48) == (8, 16, 32, 48)  # cache_len appended
    assert pow2_buckets(8) == (8,)
    with pytest.raises(ValueError):
        pow2_buckets(0)


# -- warmup / steady state -------------------------------------------------


def test_warmup_compiles_buckets_and_run_is_retrace_free(cfg, params):
    harness = OfflineInference(
        cfg, params, n_slots=4, cache_len=CACHE_LEN, prefill_chunk=CHUNK,
        buckets=BUCKETS, overlap=True, queue_size=8,
    )
    warm = harness.warmup()
    # one compiled extend graph per bucket width, snapshot at warmup
    assert warm["jit_traces"][0]["extend"] == len(BUCKETS)
    rep = harness.run(_requests(cfg, seed=3, n=6))
    harness.require_steady_state()  # zero steady-state retraces
    assert rep["retrace_free"]
    assert rep["requests"] == 6
    assert rep["tokens_out"] == 6 * 6
    assert rep["buckets"]["fallbacks"] == 0
    assert sum(rep["buckets"]["hits"].values()) == 6
    assert rep["overlap"]["processed"] == 6


def test_run_before_warmup_refused(cfg, params):
    harness = OfflineInference(cfg, params, n_slots=2,
                               cache_len=CACHE_LEN, buckets=BUCKETS)
    with pytest.raises(RuntimeError, match="warmup"):
        harness.run(_requests(cfg, n=1))


# -- completion pump -------------------------------------------------------


def test_pump_preserves_order_under_slow_callback():
    def slow(x):
        time.sleep(0.002)
        return x * 10

    with CompletionPump(slow, queue_size=4) as pump:
        for i in range(16):
            pump.put(i)
        pump.flush()
        assert pump.completed == [(i, i * 10) for i in range(16)]


def test_pump_bounded_queue_backpressure():
    gate = threading.Event()

    def gated(x):
        gate.wait(5.0)
        return x

    pump = CompletionPump(gated, queue_size=2)
    pump.put(0)  # worker picks this up and parks on the gate
    time.sleep(0.05)
    pump.put(1), pump.put(2)  # queue now full
    t = threading.Thread(target=pump.put, args=(3,))
    t.start()
    t.join(0.1)
    assert t.is_alive()  # producer genuinely blocked on the bound
    gate.set()
    t.join(5.0)
    assert not t.is_alive()
    pump.flush()
    pump.close()
    st = pump.stats()
    assert st["processed"] == 4
    assert st["blocked_puts"] >= 1
    assert st["max_depth"] <= 2


def test_pump_callback_error_propagates_and_drains():
    gate = threading.Event()

    def boom(x):
        if x == 0:
            gate.wait(5.0)
            raise ValueError("detokenize failed on 0")
        return x

    pump = CompletionPump(boom, queue_size=2)
    pump.put(0)  # worker picks it up and parks on the gate
    time.sleep(0.05)
    pump.put(1), pump.put(2)  # queued behind the failure
    gate.set()
    with pytest.raises(ValueError, match="failed on 0"):
        pump.flush()
    pump.close()  # error already consumed: close is clean + idempotent
    pump.close()
    # nothing after the failure completes; the backlog drained as drops
    assert pump.completed == []
    assert pump.stats()["dropped"] == 2


def test_pump_error_surfaces_from_put_without_hanging():
    def boom(x):
        if x == 2:
            raise ValueError("detokenize failed on 2")
        return x

    pump = CompletionPump(boom, queue_size=2)
    with pytest.raises(ValueError, match="failed on 2"):
        for i in range(64):  # keeps producing past the failure: the
            pump.put(i)      # error must surface from put(), and drain-
        pump.flush()         # after-error keeps the bound from deadlock
    pump.close()
    done = [x for x, _ in pump.completed]
    assert 2 not in done  # the failed item never lands in completed
    assert done[:2] == [0, 1]


def test_pump_put_after_close_refused():
    pump = CompletionPump(lambda x: x)
    pump.close()
    with pytest.raises(RuntimeError, match="closed"):
        pump.put(0)


# -- replica set -----------------------------------------------------------


def test_replica_meshes_single_device_fallback():
    assert replica_meshes(1) in ([None], )  # 1 replica, 1 device
    assert replica_meshes(3) == [None, None, None]  # 1 device can't split
    with pytest.raises(ValueError):
        replica_meshes(0)
    with pytest.raises(ValueError, match="evenly"):  # never stacked quietly
        replica_meshes(3, devices=[object()] * 4)


def test_replica_set_shared_queue_least_loaded(cfg, params):
    engines = [_engine(cfg, params, n_slots=2) for _ in range(2)]
    rs = ReplicaSet(engines)
    for r in _requests(cfg, seed=5, n=6):
        rs.submit(r)
    placed = rs.pump(0.0)
    # 2 replicas x 2 slots: exactly 4 dispatch, 2 park in the shared queue
    assert placed == 4
    assert rs.dispatched == [2, 2]  # least-loaded = even split
    assert len(rs.queue) == 2
    done = []
    t = 0.0
    while rs.busy:
        rs.pump(t)
        done.extend(rs.step_all(t))
        t += 1.0
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4, 5]
    assert sum(rs.dispatched) == 6
    assert min(rs.dispatched) >= 2  # nobody starved


def test_offline_harness_two_replicas_end_to_end(cfg, params):
    harness = OfflineInference(
        cfg, params, n_slots=2, cache_len=CACHE_LEN, prefill_chunk=CHUNK,
        buckets=BUCKETS, replicas=2, queue_size=8,
    )
    harness.warmup()
    rep = harness.run(_requests(cfg, seed=7, n=6))
    harness.require_steady_state()
    assert rep["replicas"] == 2
    assert sum(rep["dispatched"]) == 6
    assert min(rep["dispatched"]) >= 1  # both replicas served traffic
    assert rep["requests"] == 6
    assert rep["ttft_s"]["n"] == 6
    assert rep["latency_s"]["p99"] >= rep["ttft_s"]["p50"] >= 0


# -- stats guard -----------------------------------------------------------


def test_sample_stats_empty_guard():
    assert sample_stats([]) == {"n": 0, "mean": 0.0, "p50": 0.0,
                                "p95": 0.0, "p99": 0.0}
    st = sample_stats([1.0, 2.0, 3.0])
    assert st["n"] == 3 and st["p50"] == 2.0
