"""Pallas TPU kernels: dual-base Montgomery product and fused ladder step.

One Montgomery product MM(X, Y) on an (n, BLOCK_B) tile chains every RNS
primitive this framework has (core/montgomery.py documents the algebra):

    q      = x·y·(-N^{-1})    channel-wise in B       Barrett products
    digits = MRC(q)           Alg. 2 triangle          (mrc_rows)
    q'     = digits · betas   Alg. 3 dot -> B'         (_dot_rows)
    r'     = (x'y' + q'N)·M^{-1}  channel-wise in B'
    r      = extend(r')       MRC + dot back to B (+ redundant channels)

The ladder kernel fuses ONE exponent bit — two Montgomery products plus the
branchless square-and-multiply select — so the (n, B) operand tiles for
both bases stay in VMEM/registers across the whole bit instead of making
six HBM round-trips per extension.  Per-request moduli ``N`` arrive as DATA
rows (``neg``/``n_hi`` per batch column), not baked constants, so one
compiled kernel serves every modulus in a batch — that is what lets the
serve engine mix crypto requests with different ``N`` in the same slots.

Invariants (DESIGN.md §15): inputs < 2N per column ⟹ every intermediate
product < 2^30 (15-bit moduli, int32 lanes, exact Barrett-via-f32), both
MRC extensions are exact, and outputs are < 2N — so the fixed-width ladder
never wraps and matches the pure-jnp reference bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dispatch import resolve_interpret

from .common import barrett_mod, batch_block, mrc_rows, resident

__all__ = ["mont_mul_kernel_call", "mont_ladder_kernel_call"]


def _dot_rows(digits, betas, m, recip, *, n: int):
    """Alg. 3 dot against T arbitrary targets on (n, B) digit tiles.

    digits: (n, B); betas: (T, n) with betas[t, j] = (prod_{k<j} m_k) mod
    m_t; m/recip: (T, 1).  Returns (T, B) residues, each term Barrett-
    reduced so the running sum stays < 2m < 2**16.
    """
    acc = jnp.zeros((betas.shape[0], digits.shape[1]), jnp.int32)
    for j in range(n):
        d_j = digits[j : j + 1, :]                                 # (1, B)
        b_j = betas[:, j : j + 1]                                  # (T, 1)
        s = acc + barrett_mod(d_j * b_j, m, recip)
        acc = jnp.where(s >= m, s - m, s)
    return acc


def _mm_tile(xlo, xhi, ylo, yhi, neg, nhi, invt_lo, m_lo, betas_l2h,
             invt_hi, m_hi, betas_h2l, minv, *, n_lo: int, n_hi: int):
    """One Montgomery product on loaded tiles; returns (rlo, rhi).

    xlo/ylo: (nch_lo, B) — base channels first, then redundant; only the
    first n_lo rows feed q.  xhi/yhi: (n_hi, B).  neg: (n_lo, B) and
    nhi: (n_hi, B) are per-column data (the modulus N of each request).
    """
    r_lo = 1.0 / m_lo.astype(jnp.float32)
    r_hi = 1.0 / m_hi.astype(jnp.float32)
    mb, rb = m_lo[:n_lo], r_lo[:n_lo]
    q = barrett_mod(barrett_mod(xlo[:n_lo] * ylo[:n_lo], mb, rb) * neg,
                    mb, rb)
    qd = mrc_rows(q, invt_lo, mb, rb, n=n_lo)
    qp = _dot_rows(qd, betas_l2h, m_hi, r_hi, n=n_lo)          # (n_hi, B)
    t = barrett_mod(xhi * yhi, m_hi, r_hi) + barrett_mod(qp * nhi, m_hi, r_hi)
    t = jnp.where(t >= m_hi, t - m_hi, t)
    rhi = barrett_mod(t * minv, m_hi, r_hi)
    rd = mrc_rows(rhi, invt_hi, m_hi, r_hi, n=n_hi)
    rlo = _dot_rows(rd, betas_h2l, m_lo, r_lo, n=n_hi)         # (nch_lo, B)
    return rlo, rhi


def _mont_mul_kernel(xlo_ref, xhi_ref, ylo_ref, yhi_ref, neg_ref, nhi_ref,
                     invtlo_ref, mlo_ref, bl2h_ref, invthi_ref, mhi_ref,
                     bh2l_ref, minv_ref, olo_ref, ohi_ref, *,
                     n_lo: int, n_hi: int):
    rlo, rhi = _mm_tile(
        xlo_ref[...], xhi_ref[...], ylo_ref[...], yhi_ref[...],
        neg_ref[...], nhi_ref[...], invtlo_ref[...], mlo_ref[...],
        bl2h_ref[...], invthi_ref[...], mhi_ref[...], bh2l_ref[...],
        minv_ref[...], n_lo=n_lo, n_hi=n_hi)
    olo_ref[...] = rlo
    ohi_ref[...] = rhi


def _ladder_kernel(r0lo_ref, r0hi_ref, r1lo_ref, r1hi_ref, bit_ref,
                   neg_ref, nhi_ref, invtlo_ref, mlo_ref, bl2h_ref,
                   invthi_ref, mhi_ref, bh2l_ref, minv_ref,
                   o0lo_ref, o0hi_ref, o1lo_ref, o1hi_ref, *,
                   n_lo: int, n_hi: int):
    tables = (invtlo_ref[...], mlo_ref[...], bl2h_ref[...], invthi_ref[...],
              mhi_ref[...], bh2l_ref[...], minv_ref[...])
    neg, nhi = neg_ref[...], nhi_ref[...]
    r0lo, r0hi = r0lo_ref[...], r0hi_ref[...]
    r1lo, r1hi = r1lo_ref[...], r1hi_ref[...]
    k = bit_ref[...] == 0                                      # (1, B)
    t_lo, t_hi = _mm_tile(r0lo, r0hi, r1lo, r1hi, neg, nhi, *tables,
                          n_lo=n_lo, n_hi=n_hi)
    sqlo = jnp.where(k, r0lo, r1lo)
    sqhi = jnp.where(k, r0hi, r1hi)
    s_lo, s_hi = _mm_tile(sqlo, sqhi, sqlo, sqhi, neg, nhi, *tables,
                          n_lo=n_lo, n_hi=n_hi)
    o0lo_ref[...] = jnp.where(k, s_lo, t_lo)
    o0hi_ref[...] = jnp.where(k, s_hi, t_hi)
    o1lo_ref[...] = jnp.where(k, t_lo, s_lo)
    o1hi_ref[...] = jnp.where(k, t_hi, s_hi)


def _specs(nch_lo, n_lo, n_hi, block_b):
    blk = functools.partial(batch_block, block_b=block_b)
    tables = [resident(s) for s in ((n_lo, n_lo), (nch_lo, 1), (n_hi, n_lo),
                                    (n_hi, n_hi), (n_hi, 1), (nch_lo, n_hi),
                                    (n_hi, 1))]
    return blk, tables


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def mont_mul_kernel_call(xlo_t, xhi_t, ylo_t, yhi_t, neg_t, nhi_t,
                         invt_lo, m_lo, betas_l2h, invt_hi, m_hi, betas_h2l,
                         minv, *, block_b: int = 256,
                         interpret: bool | None = None):
    """One batched Montgomery product; operands channel-major (rows, B).

    Returns ``(olo (nch_lo, B), ohi (n_hi, B))``.
    """
    nch_lo, B = xlo_t.shape
    n_lo, n_hi = invt_lo.shape[0], xhi_t.shape[0]
    blk, tables = _specs(nch_lo, n_lo, n_hi, block_b)
    return pl.pallas_call(
        functools.partial(_mont_mul_kernel, n_lo=n_lo, n_hi=n_hi),
        grid=(B // block_b,),
        in_specs=[blk(nch_lo), blk(n_hi), blk(nch_lo), blk(n_hi),
                  blk(n_lo), blk(n_hi)] + tables,
        out_specs=[blk(nch_lo), blk(n_hi)],
        out_shape=[jax.ShapeDtypeStruct((nch_lo, B), jnp.int32),
                   jax.ShapeDtypeStruct((n_hi, B), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(xlo_t, xhi_t, ylo_t, yhi_t, neg_t, nhi_t,
      invt_lo, m_lo, betas_l2h, invt_hi, m_hi, betas_h2l, minv)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def mont_ladder_kernel_call(r0lo_t, r0hi_t, r1lo_t, r1hi_t, bit_t,
                            neg_t, nhi_t, invt_lo, m_lo, betas_l2h,
                            invt_hi, m_hi, betas_h2l, minv, *,
                            block_b: int = 256, interpret: bool | None = None):
    """One fused ladder bit (two Montgomery products + select) per column.

    ``bit_t: (1, B)`` int32 exponent bits.  Returns the four updated tiles
    ``(o0lo, o0hi, o1lo, o1hi)``.
    """
    nch_lo, B = r0lo_t.shape
    n_lo, n_hi = invt_lo.shape[0], r0hi_t.shape[0]
    blk, tables = _specs(nch_lo, n_lo, n_hi, block_b)
    return pl.pallas_call(
        functools.partial(_ladder_kernel, n_lo=n_lo, n_hi=n_hi),
        grid=(B // block_b,),
        in_specs=[blk(nch_lo), blk(n_hi), blk(nch_lo), blk(n_hi), blk(1),
                  blk(n_lo), blk(n_hi)] + tables,
        out_specs=[blk(nch_lo), blk(n_hi), blk(nch_lo), blk(n_hi)],
        out_shape=[jax.ShapeDtypeStruct((nch_lo, B), jnp.int32),
                   jax.ShapeDtypeStruct((n_hi, B), jnp.int32),
                   jax.ShapeDtypeStruct((nch_lo, B), jnp.int32),
                   jax.ShapeDtypeStruct((n_hi, B), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(r0lo_t, r0hi_t, r1lo_t, r1hi_t, bit_t, neg_t, nhi_t,
      invt_lo, m_lo, betas_l2h, invt_hi, m_hi, betas_h2l, minv)
