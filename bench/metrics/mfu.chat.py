"""The whole serving step's share of the chip's bf16 peak: the model
operations of every token the traced span produced or prefilled
(dropless, at each token's real context, by ``bench/work``) over the
span's seconds times the peak."""


def read(run):
    if run.trace is None or run.work is None:
        return None
    a, b = run.trace_span
    flops = 0
    for s in run.window.steps:
        if a < s["t1"] <= b:
            flops += sum(run.work.token_flops(run.spec, c) for c in s["ctx"])
    for x in run.window.admits:
        if a < x["t"] <= b:
            flops += run.work.prefill_flops(run.spec, x["start"],
                                            x["plen"] - x["start"])
    if not flops:
        return None
    return 100.0 * flops / ((b - a) * run.peaks["bf16_flops"])
