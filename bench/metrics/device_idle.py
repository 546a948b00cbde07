"""Share of the traced span in which no operation ran on the chip:
1 - (union of the device operations' intervals) / (span)."""


def read(run):
    if run.trace is None:
        return None
    a, b = run.trace_span
    return 100.0 * (1.0 - run.trace.busy_s() / (b - a))
