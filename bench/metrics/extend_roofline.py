"""Share of its roofline that the bucketed extend reaches: the least time
of the admissions the traced span holds (for each, the larger of its
operations over the bf16 peak and its bytes over the HBM bandwidth,
counted dropless at its real length by ``bench/work``'s
``prefill_step``) over the device time of the extend's launches."""

MODULE = "jit__extend_paged_impl"


def read(run):
    if run.trace is None or getattr(run.work, "prefill_step", None) is None:
        return None
    t, n = run.trace.module_s(lambda name: name.startswith(MODULE))
    a, b = run.trace_span
    admits = [x for x in run.window.admits if a < x["t"] <= b]
    if not n or not admits:
        return None
    least = 0.0
    for x in admits:
        flops, nbytes = run.work.prefill_step(run.spec, x["start"],
                                              x["plen"] - x["start"])
        least += max(flops / run.peaks["bf16_flops"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
