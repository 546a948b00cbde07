"""The reduction from a profiler trace to busy time, module time and
idle gaps, checked by hand: on intervals made up here, and on a small
trace recorded on a v5e (``data/v5e_trace.xplane.pb``, written by
``data/record_trace.py``: three rounds of a 2048x2048 bf16 matmul step
inside a ``bench.decode`` span, then a small elementwise step inside a
``bench.admit`` span, then a 2 ms sleep)."""
import os

import smoke  # noqa: F401
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "v5e_trace.xplane.pb")


def test_union_and_total_by_hand():
    ivs = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert tr.union(ivs) == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.total(tr.union(ivs)) == 3.0
    assert tr.module_name("jit__decode_paged_impl(123)") == \
        "jit__decode_paged_impl"


def test_busy_modules_and_gaps_by_hand():
    t = tr.Trace(
        modules={"jit_a": [(0.0, 1.0), (2.0, 3.0)], "jit_b": [(5.0, 5.5)]},
        ops={"/device:TPU:0": [(0.0, 1.0), (2.0, 3.0), (5.0, 5.5)],
             "/device:TPU:1": [(0.0, 0.5)]},
        spans=[("bench.admit", 0.9, 2.1), ("bench.decode", 3.0, 5.0)],
        chips=["/device:TPU:0", "/device:TPU:1"])
    assert t.busy_s() == (2.5 + 0.5) / 2
    assert t.module_s(lambda n: n == "jit_a") == (2.0, 2)
    assert t.top_modules() == [["jit_a", 2.0], ["jit_b", 0.5]]
    # gaps on chip 0: (1, 2) under bench.admit, (3, 5) under bench.decode
    assert t.idle_gaps() == [["bench.decode", 2.0], ["bench.admit", 1.0]]


def test_recorded_v5e_trace():
    t = tr.Trace.from_file(DATA)
    assert t.chips == ["/device:TPU:0"]
    mm, n_mm = t.module_s(lambda n: n == "jit_matmul_step")
    sm, n_sm = t.module_s(lambda n: n == "jit_small_step")
    assert (n_mm, n_sm) == (3, 3)
    # the modules' durations as listed in the trace, in ns
    assert abs(mm - (102367 + 102592 + 102386) * 1e-9) < 1e-12
    assert abs(sm - (25702 + 25142 + 25106) * 1e-9) < 1e-12
    # 2 x 2048^3 operations at no more than the 197 TFLOP/s peak
    assert mm / 3 > 2 * 2048**3 / 197e12
    # busy: the union of the 12 ops (copy-start, copy-done, the matmul
    # fusion; the tanh fusion), a few ns under the modules' sum
    assert len(t.ops[t.chips[0]]) == 12
    assert abs(t.busy_s() - 383266e-9) < 1e-12
    assert t.busy_s() <= mm + sm
    names = [s[0] for s in t.spans]
    assert names == ["bench.decode", "bench.admit"] * 3
    # device and host clocks differ by a steady offset (about -1.2 ms on
    # this trace): each small step starts that far before its admit span
    admits = [s for s in t.spans if s[0] == "bench.admit"]
    offs = [iv[0] - a for iv, (_, a, _) in zip(t.modules["jit_small_step"],
                                               admits)]
    assert max(offs) - min(offs) < 1e-4 and -2e-3 < offs[0] < 0
    # the chip idles between the rounds, during the host's sleep
    gaps = dict(t.idle_gaps())
    assert sum(gaps.values()) > 2 * 0.002
