"""Shared in-kernel primitives for the RNS Pallas kernels.

TPU adaptation notes (DESIGN.md §3):

* Layout is **(n, B)** — channels on sublanes, batch on the 128-wide lane
  axis.  The paper parallelizes one conversion across channels; on TPU the
  VPU's width is better spent across batch elements, with the short channel
  axis resident in registers/sublanes.
* Modular reduction is **Barrett-via-f32**: ``q = floor(t * (1/m))`` with a
  single ±m correction pass.  With 15-bit moduli every intermediate product
  t < 2**30, the f32 quotient error is < 1/2, so one conditional add and one
  conditional subtract make the result exact.  This replaces integer
  division/remainder, which the VPU lowers slowly.
* Every value is int32 or f32, stated explicitly: ``import repro`` turns x64
  on, and Mosaic refuses a kernel that holds a 64-bit value (a Python int
  literal, an index-map zero, or a ``jnp.sum`` default would otherwise
  become int64).
* Loops over channels are static Python unrolls with static slices: ``n``
  is a small trace-time constant, and Mosaic has no lowering for a
  traced-index ``dynamic_slice``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["barrett_mod", "mrc_rows", "to_ma_rows", "batch_block", "resident"]

_I0 = np.int32(0)


def batch_block(rows: int, block_b: int) -> pl.BlockSpec:
    """A (rows, block_b) tile walking the batch (lane) axis with the grid."""
    return pl.BlockSpec((rows, block_b), lambda b: (_I0, b))


def resident(shape) -> pl.BlockSpec:
    """A whole small table, the same block at every grid step."""
    return pl.BlockSpec(shape, lambda b: (_I0, _I0))


def barrett_mod(t, m, recip):
    """Exact t mod m for 0 <= t < 2**30, m < 2**15 (all int32, f32 recip)."""
    q = jnp.floor(t.astype(jnp.float32) * recip).astype(jnp.int32)
    r = t - q * m
    r = jnp.where(r < 0, r + m, r)
    r = jnp.where(r >= m, r - m, r)
    return r


def mrc_rows(w, inv_t, m, recip, *, n: int):
    """Alg. 2 on an (n, B) register tile.

    w:      (n, B) residues
    inv_t:  (n, n) transposed inverse table: inv_t[i, j] = m_j^{-1} mod m_i
    m:      (n, 1) moduli;  recip: (n, 1) f32 reciprocals
    Returns (n, B) mixed-radix digits.
    """
    idx = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    for j in range(n - 1):
        a_j = w[j : j + 1, :]                                      # (1, B)
        inv_j = inv_t[:, j : j + 1]                                # (n, 1)
        d = w - a_j
        d = jnp.where(d < 0, d + m, d)
        r = barrett_mod(d * inv_j, m, recip)
        w = jnp.where(idx > j, r, w)
    return w


def to_ma_rows(digits, betas, ma: int):
    """Alg. 3 on an (n, B) digit tile -> (1, B) residues mod m_a.

    betas: (n, 1) partial products mod m_a.  Per-term reduction keeps the
    row-sum < n * m_a < 2**31.
    """
    recip = jnp.float32(1.0 / ma)
    terms = barrett_mod(digits * betas, jnp.int32(ma), recip)
    s = jnp.sum(terms, axis=0, keepdims=True, dtype=jnp.int32)  # (1, B)
    return barrett_mod(s, jnp.int32(ma), recip)
