"""Pallas TPU kernel: channel-wise modular multiply (RNS ring product).

The throughput workhorse of every RNS pipeline (the paper's op-count unit
``M``).  Elementwise over an (n, B) tile; Barrett-via-f32 reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dispatch import resolve_interpret

from .common import barrett_mod, batch_block, resident

__all__ = ["modmul_kernel_call"]


def _kernel(x_ref, y_ref, m_ref, out_ref):
    m = m_ref[...]
    recip = 1.0 / m.astype(jnp.float32)
    out_ref[...] = barrett_mod(x_ref[...] * y_ref[...], m, recip)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def modmul_kernel_call(x_t, y_t, m_col, *, block_b: int = 1024,
                       interpret: bool | None = None):
    """x_t, y_t: (n, B) int32 reduced residues -> (n, B) product residues."""
    n, B = x_t.shape
    grid = (B // block_b,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[batch_block(n, block_b), batch_block(n, block_b),
                  resident((n, 1))],
        out_specs=batch_block(n, block_b),
        out_shape=jax.ShapeDtypeStruct((n, B), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x_t, y_t, m_col)
