"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read, with nothing but ``jax.profiler.ProfileData``.

* device busy time: the union of the intervals in which an operation ran
  on each TPU (line ``XLA Ops``; ``XLA Modules`` where that is missing),
  averaged over the chips;
* device time per XLA module, by the module's name (the jitted
  function's name, ``jit_<fn>``, without the ``(id)`` suffix);
* the benchmark's own host spans (``TraceAnnotation`` names starting
  ``bench.``), on the same clock as the device events;
* the idle gaps of the first chip, each named after the host span that
  covers most of it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(logdir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    return hits[-1] if hits else None


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals; the result is sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def module_name(name: str) -> str:
    return _SUFFIX.sub("", name.strip())


class Trace:
    """What one trace holds, in seconds on the trace's clock."""

    def __init__(self, modules, ops, spans, chips):
        self.modules = modules    # name -> [(start, end)] (first chip)
        self.ops = ops            # chip -> [(start, end)] of device ops
        self.spans = spans        # [(name, start, end)] host spans
        self.chips = chips

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        modules: dict[str, list] = defaultdict(list)
        ops: dict[str, list] = {}
        spans = []
        chips = []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                lines = {l.name: l for l in plane.lines}
                chips.append(plane.name)
                busy_line = lines.get("XLA Ops") or lines.get("XLA Modules")
                ops[plane.name] = [
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in (busy_line.events if busy_line else [])]
                if len(chips) == 1 and "XLA Modules" in lines:
                    for e in lines["XLA Modules"].events:
                        modules[module_name(e.name)].append(
                            (e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            spans.append((e.name, e.start_ns * 1e-9,
                                          (e.start_ns + e.duration_ns) * 1e-9))
        chips.sort()
        return cls(dict(modules), ops, sorted(spans, key=lambda s: s[1]),
                   chips)

    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips."""
        if not self.chips:
            return 0.0
        return sum(total(union(self.ops[c])) for c in self.chips) / len(
            self.chips)

    def module_s(self, pred) -> tuple[float, int]:
        """(device seconds, launches) of the modules whose name passes."""
        t, n = 0.0, 0
        for name, ivs in self.modules.items():
            if pred(name):
                t += total(ivs)
                n += len(ivs)
        return t, n

    def top_modules(self, k: int = 10) -> list:
        rows = [[name, total(ivs)] for name, ivs in self.modules.items()]
        return sorted(rows, key=lambda r: -r[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle time of the first chip between its first and last
        operation, summed by the host span that covers most of each gap
        (``host`` where none does); the ``k`` largest."""
        if not self.chips:
            return []
        busy = union(self.ops[self.chips[0]])
        by: dict[str, float] = defaultdict(float)
        j = 0
        for (s0, e0), (s1, _) in zip(busy, busy[1:]):
            gs, ge = e0, s1
            best, cover = "host", 0.0
            while j < len(self.spans) and self.spans[j][2] < gs:
                j += 1
            for name, ss, se in self.spans[j:]:
                if ss > ge:
                    break
                ov = min(se, ge) - max(ss, gs)
                if ov > cover:
                    best, cover = name, ov
            by[best] += ge - gs
        rows = [[n, t] for n, t in by.items()]
        return sorted(rows, key=lambda r: -r[1])[:k]
