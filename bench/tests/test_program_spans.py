"""The split of the chip's idle time by the innermost program span,
checked by hand on intervals made up here, and on a small trace recorded
on a v5e (``data/v5e_program_trace.xplane.pb``, written by
``data/record_program_trace.py``: a tiny paged engine with ``rns_verify``
serving five requests with evictions)."""
import collections
import os
import shutil
import types

import pytest

import smoke  # noqa: F401
import harness
import program_spans as ps
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "v5e_program_trace.xplane.pb")


def _split(busy, spans):
    return ps.split_idle(ps.idle_gaps(tr.union(busy)), ps.innermost(spans))


def test_nested_spans_give_each_instant_to_the_innermost():
    spans = [("serve.step", 0.0, 10.0), ("serve.write_barrier", 1.0, 4.0),
             ("serve.fp.verify", 2.0, 3.0), ("serve.fp.verify", 6.0, 7.0)]
    assert ps.innermost(spans) == [
        (0.0, 1.0, "step"), (1.0, 2.0, "barrier"), (2.0, 3.0, "fp"),
        (3.0, 4.0, "barrier"), (4.0, 6.0, "step"), (6.0, 7.0, "fp"),
        (7.0, 10.0, "step")]
    # the chip busy at the very start and end: idle over (0.5, 9.5)
    by = _split([(0.0, 0.5), (9.5, 10.0)], spans)
    assert by == {"fp": 2.0, "barrier": 2.0, "admit": 0.0, "step": 5.0,
                  "outside": 0.0}


def test_a_gap_partly_covered_and_a_gap_with_no_span():
    spans = [("serve.admit", 1.0, 2.0), ("serve.fp.publish", 1.5, 1.75),
             ("bench.decode", 5.0, 6.0)]          # not a program span
    # gaps (0.5, 3) and (4, 8); the second has no program span
    by = _split([(0.0, 0.5), (3.0, 4.0), (8.0, 9.0)], spans)
    assert by["admit"] == 0.75 and by["fp"] == 0.25
    assert by["outside"] == (3.0 - 0.5 - 1.0) + 4.0
    assert sum(by.values()) == 2.5 + 4.0


def test_spans_straddling_the_edges_of_the_trace():
    # a step open before the first operation and one still open after
    # the last: only the idle time between the two counts
    spans = [("serve.step", -5.0, 1.5), ("serve.step", 3.5, 20.0)]
    by = _split([(1.0, 2.0), (3.0, 4.0)], spans)
    assert by == {"fp": 0.0, "barrier": 0.0, "admit": 0.0, "step": 0.0,
                  "outside": 1.0}
    by = _split([(0.0, 1.0), (3.0, 4.0)], spans)
    assert by["step"] == 0.5 and by["outside"] == 1.5


def test_codeword_time_and_a_program_without_spans():
    spans = [("serve.fp.publish", 0.0, 0.004, {"codewords": 4}),
             ("serve.fp.verify", 1.0, 1.002, {"codewords": 1}),
             ("serve.step", 0.0, 2.0, {})]
    assert ps.ms_per_codeword(spans) == pytest.approx(6.0 / 5)
    assert ps.ms_per_codeword(spans[2:]) is None
    assert ps.ms_per_codeword(None) is None


def _recorded_run(monkeypatch):
    """A run record over the recorded trace, as a reader is handed it."""
    trace = tr.Trace.from_file(DATA)
    monkeypatch.setattr(ps, "run_spans", lambda run: ps.spans_in(DATA))
    return types.SimpleNamespace(trace=trace, trace_span=(0.0, 1.0),
                                 cell={"name": "probe"})


def test_recorded_v5e_trace(monkeypatch):
    spans = ps.spans_in(DATA)
    count = collections.Counter(s[0] for s in spans)
    assert count == {"serve.admit": 20, "serve.step": 20,
                     "serve.write_barrier": 11, "serve.fp.verify": 10,
                     "serve.fp.publish": 5}
    codewords = collections.Counter()
    for name, _, _, stats in spans:
        if name.startswith("serve.fp."):
            codewords[name] += stats["codewords"]
    run = _recorded_run(monkeypatch)
    # each codeword is one fingerprint launch on the chip, and each
    # verified one a comparison
    launches = {n: len(v) for n, v in run.trace.modules.items()}
    assert codewords == {"serve.fp.verify": 15, "serve.fp.publish": 10}
    assert launches["jit__fp_paged_impl"] == 15 + 10
    assert launches["jit_equal"] == 15
    assert launches["jit__extend_paged_impl"] == 5
    busy = tr.union(run.trace.ops[run.trace.chips[0]])
    gaps = ps.idle_gaps(busy)
    by = ps.idle_by_layer(run)
    # an exact split: the parts add up to the idle time between the
    # first and the last operation
    assert sum(by.values()) == pytest.approx(tr.total(gaps), rel=1e-9)
    assert all(v >= 0 for v in by.values())
    assert by["fp"] > 0 and by["step"] > 0 and by["admit"] > 0
    # the readers, each over the same run
    got = {name: harness.metric_reader(name).read(run) for name in (
        "fp_idle.chat", "barrier_idle.chat", "admit_idle.chat",
        "step_idle.chat", "fp_ms_per_codeword.chat")}
    assert all(harness.finite(v) and v >= 0 for v in got.values())
    assert got["fp_idle.chat"] == pytest.approx(100.0 * by["fp"])
    assert 0 < got["fp_ms_per_codeword.chat"] < 100


def test_readers_read_nothing_without_program_spans(monkeypatch, tmp_path):
    """No trace, or a trace of a program that opens no ``serve.*`` span
    (``data/v5e_trace.xplane.pb``, ``bench.*`` spans only): every reader gives
    nothing, and none raises."""
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    names = ("fp_idle.chat", "barrier_idle.chat", "admit_idle.chat",
             "step_idle.chat", "fp_ms_per_codeword.chat")
    run = types.SimpleNamespace(trace=None, trace_span=(0.0, 1.0),
                                cell={"name": "qmoe.chat-over"})
    for name in names:
        assert harness.metric_reader(name).read(run) is None
    old = os.path.join(os.path.dirname(DATA), "v5e_trace.xplane.pb")
    where = tmp_path / ".bench_trace" / "qmoe.chat-over" / "plugins" / \
        "profile" / "t"
    where.mkdir(parents=True)
    shutil.copy(old, where / "old.xplane.pb")
    run.trace = tr.Trace.from_file(old)
    assert run.trace.spans and run.trace.chips
    for name in names:
        assert harness.metric_reader(name).read(run) is None
