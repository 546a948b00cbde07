"""The serving engine's profiler spans (``serve.*`` TraceAnnotations).

A tiny paged engine with ``rns_verify`` and a pool small enough that
pages are evicted runs under ``jax.profiler.trace``; the trace is read
back with ``jax.profiler.ProfileData``.  The spans mark where the host
makes the chip wait (admission, the decode step, the paged write
barrier, fingerprint publish and verify), and the fingerprint spans
carry the number of RRNS codewords they handled as the stat
``codewords``.
"""
import glob
import os

import jax
import pytest

import repro  # noqa: F401
from conftest import N_PG, make_engine
from repro.serve.scheduler import Request

SPANS = {"serve.admit", "serve.step", "serve.write_barrier",
         "serve.fp.publish", "serve.fp.verify"}
BUCKETS = (8, 16, 32)


def _requests():
    """Five prompts over 6 usable pages of 8: the third admission's
    write barrier evicts two retained prompt pages in one action list."""
    return [Request(rid=i, prompt=[i * 3 + 2] * n, max_new=4)
            for i, n in enumerate((24, 8, 24, 8, 15))]


def _engine(cfg, params):
    return make_engine(cfg, params, n_slots=2, n_pages=N_PG + 2,
                       rns_verify=True, buckets=BUCKETS)


def _host_events(logdir):
    """(name, start_ns, end_ns, stats) of every host event, by start;
    the stats of the ``serve.*`` spans only."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    stats = (dict(e.stats) if e.name.startswith("serve.")
                             else {})
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, stats))
    return sorted(out, key=lambda x: x[1])


@pytest.fixture(scope="module")
def traced(cfg, params, tmp_path_factory):
    """One traced run: the engine, its host events, the codewords put,
    and the number of calls of the batched fingerprint graph."""
    eng = _engine(cfg, params)
    puts, fp_calls = [], []
    put, fp = eng.wire.put, eng._fp_fn
    eng.wire.put = lambda key, arr: (puts.append(key), put(key, arr))[1]
    eng._fp_fn = lambda *a: (fp_calls.append(1), fp(*a))[1]
    logdir = str(tmp_path_factory.mktemp("trace"))
    try:
        with jax.profiler.trace(logdir):
            for r in _requests():
                eng.submit(r)
            done = eng.run_to_completion()
    finally:
        eng._fp_fn = fp
    return {"eng": eng, "events": _host_events(logdir), "puts": puts,
            "fp_calls": fp_calls,
            "tokens": {r.rid: list(r.out) for r in done}}


def _spans(traced, name):
    return [e for e in traced["events"] if e[0] == name]


def test_every_span_appears_and_nests(traced):
    assert traced["eng"].page_stats()["pages_evicted"] >= 1
    names = {e[0] for e in traced["events"] if e[0].startswith("serve.")}
    assert names == SPANS
    outer = _spans(traced, "serve.step") + _spans(traced, "serve.admit")
    for name in ("serve.fp.verify", "serve.write_barrier",
                 "serve.fp.publish"):
        for _, s, e, _ in _spans(traced, name):
            assert any(a <= s and e <= b for _, a, b, _ in outer), name
    # a barrier's eviction verify opens inside the barrier, once for
    # every page the action list evicts
    barriers = _spans(traced, "serve.write_barrier")
    evict_verifies = [v for v in _spans(traced, "serve.fp.verify")
                      if any(a <= v[1] and v[2] <= b
                             for _, a, b, _ in barriers)]
    assert max(v[3]["codewords"] for v in evict_verifies) == 2


def test_codewords_match_the_wire_counts(traced):
    eng = traced["eng"]
    verified = sum(st["codewords"]
                   for *_, st in _spans(traced, "serve.fp.verify"))
    published = sum(st["codewords"]
                    for *_, st in _spans(traced, "serve.fp.publish"))
    assert verified == eng.wire.stats["verified"] > 0
    assert eng.wire.stats["failed"] == 0
    assert published == len(traced["puts"]) > 0
    assert all(eng.verify_log.values())


def test_one_fingerprint_graph_call_per_paged_span(traced):
    """Each LLM ``serve.fp.*`` span makes ONE call of the batched
    fingerprint graph, whatever its number of codewords."""
    spans = (_spans(traced, "serve.fp.publish")
             + _spans(traced, "serve.fp.verify"))
    assert max(st["codewords"] for *_, st in spans) > 1
    assert all(st["codewords"] > 0 for *_, st in spans)
    assert len(traced["fp_calls"]) == len(spans)
    # a jitted call leaves two nested host events of its name: count the
    # outer ones
    graph = {(e[1], e[2]) for e in traced["events"]
             if e[0] == "PjitFunction(_fp_pages_impl)"}
    calls = [g for g in graph if not any(
        h != g and h[0] <= g[0] and g[1] <= h[1] for h in graph)]
    for _, s, e, _ in spans:
        assert sum(s <= a and b <= e for a, b in calls) == 1


def test_tokens_identical_with_the_profiler_on_and_off(cfg, params, traced):
    eng = _engine(cfg, params)
    for r in _requests():
        eng.submit(r)
    untraced = {r.rid: list(r.out) for r in eng.run_to_completion()}
    assert untraced == traced["tokens"]
    assert len(untraced) == len(_requests())


def test_one_named_extend_graph_per_bucket_width(traced):
    eng = traced["eng"]
    hits = eng.bucket_stats()["hits"]
    widths = [b for b in BUCKETS if hits[str(b)]]
    assert widths == list(BUCKETS)
    assert eng.jit_cache_sizes()["extend"] == len(widths)
    host = {e[0] for e in traced["events"]}
    assert "PjitFunction(_extend_paged_impl)" in host
    assert "PjitFunction(<lambda>)" not in host


def test_monolithic_and_crypto_codewords_match_the_wire_counts(
        cfg, params, tmp_path):
    """The prompt-page codewords of an engine with a crypto lane and the
    lane's slot codeword are published and verified under the same
    spans: every codeword put is counted once by a publish span and once
    by a verify span."""
    from repro.serve.crypto import CryptoRequest

    eng = make_engine(cfg, params, n_slots=2, rns_verify=True,
                      crypto_slots=1, crypto_chunk=8)
    puts = []
    put = eng.wire.put
    eng.wire.put = lambda key, arr: (puts.append(key), put(key, arr))[1]
    with jax.profiler.trace(str(tmp_path)):
        for r in _requests()[:2]:
            eng.submit(r)
        eng.submit(CryptoRequest(rid=9, op="modexp", a=777, b=4321,
                                 n=1000003))
        done = eng.run_to_completion()
    events = [e for e in _host_events(str(tmp_path))
              if e[0].startswith("serve.fp.")]
    codewords = {"serve.fp.publish": 0, "serve.fp.verify": 0}
    for name, *_, st in events:
        codewords[name] += st["codewords"]
    # 24 and 8 prompt tokens fill 3 + 1 pages of 8; one modexp slot
    assert len(puts) == 3 + 1 + 1 and ("crypto", 9) in puts
    assert codewords["serve.fp.publish"] == len(puts)
    assert codewords["serve.fp.verify"] == eng.wire.stats["verified"] == 5
    by_rid = {r.rid: r for r in done}
    assert set(by_rid) == {0, 1, 9}
    assert by_rid[9].result == pow(777, 4321, 1000003)
