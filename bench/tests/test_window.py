"""The window's arithmetic: rates over the whole window, tails over every
request or gap, and a stall that moves the tail."""
import numpy as np

import smoke  # noqa: F401
import harness


def _req(due, times):
    return {"due": due, "times": list(times)}


def test_rate_counts_every_token_of_every_stream_in_the_window():
    reqs = [_req(0.0, [0.5, 1.0, 1.5, 12.0]),   # last token after the close
            _req(1.0, [2.0, 3.0]),               # unfinished counts too
            _req(9.0, [])]
    assert harness.tokens_in(reqs, 0.0, 10.0) == 5
    assert harness.tokens_in(reqs, 0.0, 10.0) / 10.0 == 0.5


def test_ttft_from_due_time_and_age_at_the_close():
    reqs = [_req(0.0, [0.3]), _req(1.0, [1.1]), _req(2.0, [9.0]),
            _req(8.0, []),           # no first token: its age at 10 s
            _req(-1.0, [0.5]),       # due in the pre-roll: not counted
            _req(11.0, [])]          # due after the close: not counted
    got = harness.ttft_samples(reqs, 10.0)
    assert np.allclose(sorted(got), [0.1, 0.3, 2.0, 7.0])


def test_percentiles_over_all_samples_not_chunks():
    xs = list(range(1, 101))
    assert harness.percentile(xs, 90) == np.percentile(xs, 90)
    assert harness.percentile(xs, 95) == 95.05
    # medians of chunks would hide the one slow chunk; all samples do not
    xs = [1.0] * 90 + [50.0] * 10
    assert harness.percentile(xs, 95) == 50.0


def test_a_stall_inside_the_window_moves_the_gap_tail():
    steady = [_req(0.0, np.arange(0.0, 10.0, 0.1)) for _ in range(4)]
    before = harness.percentile(harness.itl_samples(steady, 0.0, 10.0), 95)
    stalled = []
    for r in steady:
        t = np.array(r["times"])
        t[t >= 5.0] += 1.0     # one 1-second stall for every stream
        stalled.append(_req(0.0, t[t <= 10.0]))
    gaps = harness.itl_samples(stalled, 0.0, 10.0)
    assert max(gaps) > 1.0
    assert harness.percentile(gaps, 100) > before
    assert abs(before - 0.1) < 1e-9


def test_result_line_puts_checks_last():
    import json

    line = harness.result_line(correct=True, attempted=3, failed=0,
                               metrics={"x": {"value": 1.0, "unit": "s"}},
                               device={"platform": "tpu"},
                               checks=[("gap", 0.1, 0.2, True)])
    d = json.loads(line)
    assert list(d)[-1] == "checks"
    assert d["checks"]["gap"] == {"value": 0.1, "limit": 0.2, "ok": True}
