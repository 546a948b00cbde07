"""Pallas TPU kernel: FUSED gradient-codec decode (the optimizer hot path).

After the per-channel psum, every gradient element holds n+1 summed int32
channels.  Per (n+1, BLOCK_B) tile this kernel fuses:

    fold    summed -> residues            (Barrett per channel)
    MRC     residues -> digits            (Alg. 2 triangle, in-register)
    Horner  digits -> value v in [0, M)   (3x15-bit limbs, int32-exact)
    sign    v >= ceil(M/2) ? v - M : v    (limb-wise compare & subtract)
    cast    correctly-rounded f32 of the exact integer v via a Fast2Sum
            compensated limb sum — bitwise identical to the f64 jnp path

The unfused jnp path round-trips the tensor through HBM four times; fused
it is once.  Limb arithmetic bounds (all int32):

    limbs l0,l1,l2 < 2^15 represent v = l2*2^30 + l1*2^15 + l0  (M < 2^45)
    v' = v*m + d:  t0 = l0*m + d        <= (2^15-1)(2^15-1)+2^15 < 2^30
                   t1 = l1*m + (t0>>15) < 2^30
                   t2 = l2*m + (t1>>15) < 2^30, requires l2 < 2^15 i.e.
                   every partial value < 2^45 — guaranteed since M < 2^45.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dispatch import resolve_interpret

from .common import barrett_mod, batch_block, mrc_rows, resident

__all__ = ["codec_decode_kernel_call"]

_MASK = 0x7FFF


def _kernel(x_ref, invt_ref, m_ref, half_ref, out_ref, *, n, inv_scale):
    m = m_ref[...]                         # (n, 1)
    recip = 1.0 / m.astype(jnp.float32)
    res = barrett_mod(x_ref[...][:n, :], m, recip)         # fold
    digits = mrc_rows(res, invt_ref[...], m, recip, n=n)   # Alg. 2

    # Horner over the mixed radix, most-significant digit first.
    l0 = digits[n - 1 : n, :]
    l1 = jnp.zeros_like(l0)
    l2 = jnp.zeros_like(l0)
    for i in range(n - 2, -1, -1):
        mi = m[i : i + 1, :]
        t0 = l0 * mi + digits[i : i + 1, :]
        t1 = l1 * mi + (t0 >> 15)
        t2 = l2 * mi + (t1 >> 15)
        l0, l1, l2 = t0 & _MASK, t1 & _MASK, t2 & _MASK

    # signed fold: v >= T (= ceil(M/2), limbs in half_ref) ? v - M : v.
    # M's limbs are (T*2 - (M odd ? ... )) — we pass BOTH T and M limbs:
    # half_ref is (6, 1): rows 0..2 = T limbs, rows 3..5 = M limbs.
    h = half_ref[...]
    t0c, t1c, t2c = h[0:1], h[1:2], h[2:3]
    m0c, m1c, m2c = h[3:4], h[4:5], h[5:6]
    ge = (
        (l2 > t2c)
        | ((l2 == t2c) & (l1 > t1c))
        | ((l2 == t2c) & (l1 == t1c) & (l0 >= t0c))
    )
    # v - M with borrows (only where ge)
    b0 = l0 - m0c
    bor0 = (b0 < 0).astype(jnp.int32)
    b1 = l1 - m1c - bor0
    bor1 = (b1 < 0).astype(jnp.int32)
    b2 = l2 - m2c - bor1
    s0 = jnp.where(ge, b0 + (bor0 << 15), l0)
    s1 = jnp.where(ge, b1 + (bor1 << 15), l1)
    s2 = jnp.where(ge, b2, l2)
    # Correctly-rounded f32 of v = s2*2^30 + s1*2^15 + s0 (s2 may be
    # negative after the signed fold).  Each term is exact in f32; naive
    # summation double-rounds, so compensate: Fast2Sum(a2, a1) is valid
    # because |a2| >= 2^30 > |a1| whenever s2 != 0 (and exact trivially at
    # s2 == 0), and the residual e1 + a0 is an integer < 2^24, hence exact.
    # The final add then rounds the EXACT v once — matching the jnp path's
    # f64->f32 cast bit for bit (inv_scale is a power of two: exact).
    a2 = s2.astype(jnp.float32) * jnp.float32(float(1 << 30))
    a1 = s1.astype(jnp.float32) * jnp.float32(float(1 << 15))
    a0 = s0.astype(jnp.float32)
    t1 = a2 + a1
    e1 = a1 - (t1 - a2)
    val = t1 + (e1 + a0)
    out_ref[...] = val * jnp.float32(inv_scale)


@functools.partial(
    jax.jit, static_argnames=("n", "inv_scale", "block_b", "interpret")
)
def codec_decode_kernel_call(
    x_t, inv_t, m_col, half_col, *, n: int, inv_scale: float,
    block_b: int = 1024, interpret: bool | None = None,
):
    """x_t: (n+1, B) int32 summed channels -> (1, B) f32 gradients."""
    nch, B = x_t.shape
    grid = (B // block_b,)
    return pl.pallas_call(
        functools.partial(_kernel, n=n, inv_scale=inv_scale),
        grid=grid,
        in_specs=[batch_block(nch, block_b), resident((n, n)),
                  resident((n, 1)), resident((6, 1))],
        out_specs=batch_block(1, block_b),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x_t, inv_t, m_col, half_col)
