"""Share of the engine's LLM slots that decode, averaged over every
decode step of the window (the scheduler's own slot states, read by the
benchmark's loop before each step)."""


def read(run):
    steps = run.window.steps
    if not steps:
        return None
    slots = run.spec["engine"]["n_slots"]
    return 100.0 * sum(len(s["ctx"]) for s in steps) / (slots * len(steps))
