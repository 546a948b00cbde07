"""Device time per launch of the engine's paged decode step, from the
trace's XLA modules (the step is ``jax.jit`` of
``ContinuousBatcher._decode_paged_impl``)."""

MODULE = "jit__decode_paged_impl"


def read(run):
    if run.trace is None:
        return None
    t, n = run.trace.module_s(lambda name: name.startswith(MODULE))
    return 1e3 * t / n if n else None
