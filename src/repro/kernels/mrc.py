"""Pallas TPU kernel: batched Mixed-Radix Conversion (paper Alg. 2).

Grid: 1-D over batch blocks.  Each program instance holds an
(n, BLOCK_B) residue tile plus the (n, n) inverse table in VMEM and runs the
triangular recurrence entirely in registers — n(n-1)/2 modular mults per
element with zero HBM round-trips between steps.

VMEM budget (int32): n*BLOCK_B + n*n + O(n) words.  With the default
BLOCK_B=512 and n<=128: 128*512*4 = 256 KiB tile + 64 KiB table — far under
the ~16 MiB v5e VMEM, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dispatch import resolve_interpret

from .common import batch_block, mrc_rows, resident

__all__ = ["mrc_kernel_call"]


def _kernel(x_ref, invt_ref, m_ref, out_ref, *, n: int):
    w = x_ref[...]
    m = m_ref[...]                       # (n, 1)
    recip = 1.0 / m.astype(jnp.float32)
    out_ref[...] = mrc_rows(w, invt_ref[...], m, recip, n=n)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def mrc_kernel_call(x_t, inv_t, m_col, *, block_b: int = 512,
                    interpret: bool | None = None):
    """x_t: (n, B) int32 residues (channel-major).  Returns (n, B) digits.

    B must be a multiple of block_b (ops.py pads).
    """
    n, B = x_t.shape
    grid = (B // block_b,)
    return pl.pallas_call(
        functools.partial(_kernel, n=n),
        grid=grid,
        in_specs=[batch_block(n, block_b), resident((n, n)), resident((n, 1))],
        out_specs=batch_block(n, block_b),
        out_shape=jax.ShapeDtypeStruct((n, B), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x_t, inv_t, m_col)
