"""Pallas TPU kernel: FUSED Algorithm 1 (the paper's comparison).

One pass per (n, BLOCK_B) tile:

    z      = (x1 - x2) mod m_i          channel-wise subtract
    digits = MRC(z)                     Alg. 2, in-register triangle
    Delta  = to_ma(digits)              Alg. 3 dot against betas
    Delta' = (xa1 - xa2) mod m_a        redundant channel
    out    = (Delta == Delta')          verdict (int32 0/1)

Fusing all four stages keeps the digit tensor entirely in VMEM/registers —
the unfused path writes/reads the (B, n) digit tensor through HBM twice.
This kernel is the framework's hot path for element-wise magnitude tests on
RNS-coded tensors (gradient codec sign/clip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dispatch import resolve_interpret

from .common import batch_block, mrc_rows, resident, to_ma_rows

__all__ = ["compare_kernel_call"]


def _kernel(
    x1_ref, xa1_ref, x2_ref, xa2_ref, invt_ref, m_ref, betas_ref, out_ref, *, n, ma
):
    m = m_ref[...]                       # (n, 1)
    recip = 1.0 / m.astype(jnp.float32)
    z = x1_ref[...] - x2_ref[...]
    z = jnp.where(z < 0, z + m, z)                         # line 2 of Alg. 1
    digits = mrc_rows(z, invt_ref[...], m, recip, n=n)     # line 3
    delta = to_ma_rows(digits, betas_ref[...], ma)         # line 4, (1, B)
    dp = xa1_ref[...] - xa2_ref[...]
    dp = jnp.where(dp < 0, dp + ma, dp)                    # line 1
    out_ref[...] = (delta == dp).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("ma", "block_b", "interpret"))
def compare_kernel_call(
    x1_t, xa1, x2_t, xa2, inv_t, m_col, betas_col, *, ma: int,
    block_b: int = 512, interpret: bool | None = None,
):
    """x*_t: (n, B) residues; xa*: (1, B) redundant residues.

    Returns (1, B) int32 verdicts (1 where N1 >= N2).
    """
    n, B = x1_t.shape
    grid = (B // block_b,)
    blk = functools.partial(batch_block, block_b=block_b)
    return pl.pallas_call(
        functools.partial(_kernel, n=n, ma=ma),
        grid=grid,
        in_specs=[blk(n), blk(1), blk(n), blk(1), resident((n, n)),
                  resident((n, 1)), resident((n, 1))],
        out_specs=blk(1),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x1_t, xa1, x2_t, xa2, inv_t, m_col, betas_col)
