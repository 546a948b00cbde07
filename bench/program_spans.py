"""The program's own profiler spans in a run's trace, and the chip's idle
time split by the span the host was in.

The serving engine opens ``jax.profiler.TraceAnnotation`` spans named
``serve.*`` where the host makes the chip wait: ``serve.admit``,
``serve.step``, ``serve.write_barrier``, ``serve.fp.publish`` and
``serve.fp.verify`` (the last two with the stat ``codewords``).  They are
host events on the same clock as the device's, read here with nothing but
``jax.profiler.ProfileData``.

Each idle instant of the first chip, between its first and last
operation, goes to the innermost program span open at that instant:
``fp`` (either ``serve.fp.*``), ``barrier``, ``admit``, ``step``, or
``outside`` where none is (the benchmark's own loop).  A program without
these spans reads as nothing, not as zero.
"""
from __future__ import annotations

import functools
import os

import harness
import trace_reduce

LAYERS = ("fp", "barrier", "admit", "step")


def layer_of(name: str) -> str | None:
    if name.startswith("serve.fp."):
        return "fp"
    return {"serve.write_barrier": "barrier", "serve.admit": "admit",
            "serve.step": "step"}.get(name)


@functools.lru_cache(maxsize=None)
def spans_in(path: str) -> tuple:
    """(name, start_s, end_s, stats) of every ``serve.*`` host event of
    the trace at ``path``, by start; parsed once per process."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                dict(e.stats)))
    return tuple(sorted(out, key=lambda s: s[1]))


def run_spans(run) -> tuple | None:
    """The program spans of a ``--trace 1`` run (its trace is still in
    ``<checkout>/.bench_trace/<cell>`` while the readers run), or None
    where there is no trace or the program opened no span."""
    path = trace_reduce.find_xplane(
        os.path.join(harness.ROOT, ".bench_trace", run.cell["name"]))
    return (spans_in(path) or None) if path else None


def innermost(spans) -> list[tuple[float, float, str]]:
    """Disjoint (start, end, layer) pieces of time, each under the
    innermost program span open over it (the one opened last)."""
    spans = sorted((s for s in spans if layer_of(s[0])), key=lambda s: s[1])
    points = sorted({t for _, s, e, *_ in spans for t in (s, e)})
    out: list[list] = []
    active: list = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(spans) and spans[i][1] <= a:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[2] > a]
        if not active:
            continue
        layer = layer_of(max(active, key=lambda s: (s[1], -s[2]))[0])
        if out and out[-1][1] == a and out[-1][2] == layer:
            out[-1][1] = b
        else:
            out.append([a, b, layer])
    return [tuple(p) for p in out]


def idle_gaps(busy) -> list[tuple[float, float]]:
    """The idle intervals between the first and the last of the sorted,
    disjoint busy intervals ``busy``."""
    return [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]


def split_idle(gaps, pieces) -> dict[str, float]:
    """Seconds of the sorted, disjoint ``gaps`` under each layer of the
    sorted, disjoint ``pieces``; the rest is ``outside``."""
    by = dict.fromkeys(LAYERS + ("outside",), 0.0)
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        covered = 0.0
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            ov = min(pieces[k][1], ge) - max(pieces[k][0], gs)
            by[pieces[k][2]] += ov
            covered += ov
            k += 1
        by["outside"] += (ge - gs) - covered
    return by


def idle_by_layer(run) -> dict[str, float] | None:
    """Idle seconds of the run's first chip, between its first and last
    operation, by the innermost program span open over them."""
    spans = run_spans(run)
    if spans is None or run.trace is None or not run.trace.chips:
        return None
    busy = trace_reduce.union(run.trace.ops[run.trace.chips[0]])
    return split_idle(idle_gaps(busy), innermost(spans))


def idle_share(run, layer: str) -> float | None:
    """Percentage of the traced span in which the chip is idle and the
    innermost open program span is ``layer`` (``device_idle``'s
    denominator)."""
    by = idle_by_layer(run)
    if by is None:
        return None
    a, b = run.trace_span
    return 100.0 * by[layer] / (b - a)


def ms_per_codeword(spans) -> float | None:
    """Host milliseconds per RRNS codeword published or verified: the
    summed duration of the ``serve.fp.*`` spans over their codewords."""
    fp = [s for s in spans or () if s[0].startswith("serve.fp.")]
    n = sum(s[3].get("codewords", 0) for s in fp)
    return 1e3 * sum(e - s for _, s, e, _ in fp) / n if n else None
