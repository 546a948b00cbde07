"""Share of its roofline that the paged decode step reaches: the least
time of a step (the larger of its operations over the bf16 peak and its
bytes over the HBM bandwidth, counted dropless from the rows' real
lengths by ``bench/work``) over the device time per decode launch."""

MODULE = "jit__decode_paged_impl"


def read(run):
    if run.trace is None or run.work is None:
        return None
    t, n = run.trace.module_s(lambda name: name.startswith(MODULE))
    a, b = run.trace_span
    steps = [s for s in run.window.steps if a < s["t1"] <= b]
    if not n or not steps:
        return None
    least = 0.0
    for s in steps:
        flops, nbytes = run.work.decode_step(run.spec, s["ctx"])
        least += max(flops / run.peaks["bf16_flops"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * (least / len(steps)) / (t / n)
