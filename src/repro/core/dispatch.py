"""Backend dispatch: ONE resolver for the jnp-vs-Pallas decision.

Before this module, every call site carried its own knob — ``unroll=`` on the
comparisons, ``interpret=`` on each kernel wrapper, ``fused=`` on the codec —
and three ops re-derived "are we on TPU?" independently.  Now a single
context-managed setting governs all of them:

    with repro.core.backend("pallas"):
        a >= b                    # RnsArray ops route to the fused kernels

Governed call sites: the ``RnsArray`` methods (compare/extend/mrc/mul),
the codec encode/decode paths, and the dual-base Montgomery ops in
``core.montgomery`` (``mont_mul`` / ``ladder_step`` route to the fused
``kernels.mont_ladder`` pair the same way the codec ops route to theirs).

Settings (resolution order, DESIGN.md §11):

* ``"jnp"``    — always the pure-jnp reference implementations.
* ``"pallas"`` — always the Pallas kernels (interpret-mode off TPU, so the
  same call site runs the Mosaic kernel on TPU and the interpreter on CPU).
* ``"auto"``   — the default: Pallas on TPU, jnp elsewhere (the interpreter
  is a debugging tool, not a fast path, so CPU hosts take the jitted jnp
  route).

The setting is read at TRACE time: a jitted function captures whatever
backend was active when it was traced, exactly like the static ``fused``
flag on ``GradCodec``.  Re-trace (new jit, or different static args) to
change the route of an already-compiled function.

``interpret_default()`` is the single home of the "interpret off-TPU" rule:
every ``*_kernel_call`` resolves its ``interpret=None`` default through
``resolve_interpret``; an explicit ``interpret=`` remains an override for
tests.
"""
from __future__ import annotations

import contextlib
import threading

import jax

__all__ = ["backend", "get_backend", "resolve_backend", "interpret_default",
           "resolve_interpret"]

_SETTINGS = ("jnp", "pallas", "auto")

# Thread-local so trace-time reads are safe under pjit's threaded tracing.
_state = threading.local()


def get_backend() -> str:
    """The raw active setting: "jnp" | "pallas" | "auto" (default)."""
    return getattr(_state, "setting", "auto")


def resolve_backend() -> str:
    """The effective backend for the current process: "jnp" | "pallas".

    >>> from repro.core.dispatch import backend, resolve_backend
    >>> resolve_backend() in ("jnp", "pallas")   # "auto": depends on host
    True
    >>> with backend("jnp"):
    ...     resolve_backend()
    'jnp'
    """
    setting = get_backend()
    if setting != "auto":
        return setting
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def interpret_default() -> bool:
    """Pallas kernels run interpreted off-TPU (there is no Mosaic lowering
    to run); this is the ONE definition all kernel wrappers share."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """An explicit ``interpret=`` wins; ``None`` means ``interpret_default()``,
    so no kernel call on a TPU is ever interpreted unless a caller asks."""
    return interpret_default() if interpret is None else interpret


@contextlib.contextmanager
def backend(setting: str):
    """Scoped backend override — the replacement for per-call dispatch knobs.

    >>> from repro.core.dispatch import backend, get_backend
    >>> with backend("pallas"):
    ...     get_backend()
    'pallas'
    >>> get_backend()
    'auto'
    """
    if setting not in _SETTINGS:
        raise ValueError(f"backend must be one of {_SETTINGS}, got {setting!r}")
    prev = get_backend()
    _state.setting = setting
    try:
        yield
    finally:
        _state.setting = prev
