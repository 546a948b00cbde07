"""Pallas TPU kernel: FUSED gradient-codec encode (the transport hot path).

Per (nch, BLOCK_B) tile — nch = n base channels plus one (detect) or two
(locate-and-correct) redundant channels — this kernel fuses what the jnp
path does in four HBM round-trips (f64 upcast, round/clip, per-channel mod,
redundant-channel fixup) into one pass:

    quantize  r = round(g * 2^frac_bits)        (f32, exact — see below)
    split     |r| -> hi*2^15 + lo               (exact power-of-two scales)
    clip      (hi, lo) vs qmax's limbs          (int32 compare/select)
    reduce    |q| mod m_c per channel           (Barrett, 15-bit moduli)
    embed     negate residues where r < 0; shift each redundant channel by
              its M mod m_r offset (the signed embedding of core/signed.py;
              base channels get offset 0 since m_i | M)

Exactness (all f32/int32, no 64-bit anywhere, bitwise equal to the f64
jnp path for M < 2^45):

  * g * 2^frac_bits is a power-of-two scale — exact in f32.
  * jnp.round of an f32 is exact: results < 2^24 are representable, and
    anything >= 2^24 was already an integer.  Round-half-even on the same
    real value gives the same integer as the f64 path.
  * |r| is pre-clamped to 2^44 (any such value still clips to qmax < 2^44,
    since qmax < M/2), so hi = floor(|r| * 2^-15) < 2^30 fits int32 and
    both halves of the split are exact f32 subtractions.
  * The clip compares (hi, lo) against (qmax >> 15, qmax & 0x7FFF) in
    int32 — exact at the boundary, unlike an f32 clamp at float(qmax).
  * hi < 2^30 and r_hi * (2^15 mod m) + lo < 2^30 keep every Barrett
    input in the proven range (common.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dispatch import resolve_interpret

from .common import barrett_mod, batch_block, resident

__all__ = ["codec_encode_kernel_call"]

_MASK = 0x7FFF


def _kernel(g_ref, m_ref, pow15_ref, off_ref, out_ref, *, scale, qh, ql):
    m = m_ref[...]                             # (nch, 1) base + redundant
    recip = 1.0 / m.astype(jnp.float32)

    r = jnp.round(g_ref[...] * jnp.float32(scale))  # (1, B) exact integer
    neg = r < 0.0                                   # -0.0 stays non-negative
    a = jnp.minimum(jnp.abs(r), jnp.float32(float(1 << 44)))

    hi_f = jnp.floor(a * jnp.float32(2.0 ** -15))
    lo_f = a - hi_f * jnp.float32(float(1 << 15))   # exact: |r| mod 2^15
    hi = hi_f.astype(jnp.int32)                     # < 2^30
    lo = lo_f.astype(jnp.int32)                     # < 2^15

    over = (hi > qh) | ((hi == qh) & (lo > ql))     # |q| > qmax: clip exact
    hi = jnp.where(over, jnp.int32(qh), hi)
    lo = jnp.where(over, jnp.int32(ql), lo)

    # |q| mod m_c = ((hi mod m_c) * (2^15 mod m_c) + lo) mod m_c, broadcast
    # over the channel axis; every Barrett operand stays below 2^30.
    r_hi = barrett_mod(hi, m, recip)                # (nch, B)
    r_abs = barrett_mod(r_hi * pow15_ref[...] + lo, m, recip)

    # signed embedding: (-|q|) mod m = m - (|q| mod m), except when 0
    res = jnp.where(neg & (r_abs > 0), m - r_abs,
                    jnp.where(neg, jnp.zeros_like(r_abs), r_abs))

    # redundant rows additionally shift by M mod m_r when negative: the
    # channels store q + M, so each m_r must track (q + M) mod m_r.  Base
    # rows carry off = 0 (m_i divides M), so the shift is the identity there.
    shifted = res + off_ref[...]
    shifted = jnp.where(shifted >= m, shifted - m, shifted)
    out_ref[...] = jnp.where(neg, shifted, res)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "qh", "ql", "block_b", "interpret"),
)
def codec_encode_kernel_call(
    g_row, m_all, pow15, off, *, scale: float, qh: int, ql: int,
    block_b: int = 1024, interpret: bool | None = None,
):
    """g_row: (1, B) f32 gradients -> (nch, B) int32 packed residues, where
    nch = n base + 1 or 2 redundant channels (detect vs locate-and-correct
    codecs share the kernel).

    qh/ql are qmax's 15-bit limbs (qmax = qh*2^15 + ql < 2^44); ``off`` is
    the per-channel negative-embedding shift column (0 for base rows,
    M mod m_r for redundant rows).  B must be a multiple of block_b
    (ops.py pads).
    """
    nch = m_all.shape[0]
    _, B = g_row.shape
    grid = (B // block_b,)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, qh=qh, ql=ql),
        grid=grid,
        in_specs=[batch_block(1, block_b), resident((nch, 1)),
                  resident((nch, 1)), resident((nch, 1))],
        out_specs=batch_block(nch, block_b),
        out_shape=jax.ShapeDtypeStruct((nch, B), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(g_row, m_all, pow15, off)
