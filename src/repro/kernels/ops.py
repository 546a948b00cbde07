"""Public wrappers for the RNS Pallas kernels.

These present the same (..., n) channel-minor API as repro.core, and handle:
  * layout: transpose to the kernel-native (n, B) channel-major tiles,
  * padding: batch padded to the block size (pad values are benign — every
    kernel is elementwise/per-column in batch),
  * dispatch: ``interpret=None`` (the default here and on every
    ``*_kernel_call``) resolves through ``interpret_default`` in
    core/dispatch.py, so the same call site runs the Mosaic kernel on TPU
    and the Python interpreter on CPU,
  * constraints: kernels require 15-bit (int32-lane) bases; wider bases fall
    back to the pure-jnp core implementations.

Every op also accepts ``RnsArray`` operands directly (core/array.py): pass
the typed array in place of the ``base, x[, xa]`` argument group and the
wrapper pulls the buffers/layout out itself.  ``modmul_op`` on packed
layouts then runs the kernel over ALL channels (each row reduces in its own
modulus — redundant channels included) and returns an ``RnsArray``.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from repro.core.array import RnsArray
from repro.core.base import RNSBase
from repro.core.dispatch import resolve_interpret

from .modmul import modmul_kernel_call
from .mont_ladder import mont_ladder_kernel_call, mont_mul_kernel_call
from .mrc import mrc_kernel_call
from .rns_compare import compare_kernel_call

__all__ = ["mrc_op", "modmul_op", "compare_op", "codec_encode_op",
           "codec_decode_op", "mont_mul_op", "mont_ladder_op"]


def _flatten_batch(x):
    """(..., n) -> (B, n), plus a reconstructor."""
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def _tables(base: RNSBase):
    if base.bits > 15:
        raise ValueError("Pallas kernels require bits<=15 (int32 lanes); "
                         "use repro.core for wider bases")
    inv_t = jnp.asarray(base.inv_tri_np.T, dtype=jnp.int32)        # (i, j)
    m_col = jnp.asarray(base.moduli_np[:, None], dtype=jnp.int32)  # (n, 1)
    return inv_t, m_col


def mrc_op(base, x=None, *, block_b: int = 512, interpret: bool | None = None):
    """Mixed-radix digits of ``x: (..., n)`` via the Pallas kernel.

    Also callable as ``mrc_op(arr)`` with an ``RnsArray`` — digits of the
    base channels, channels-last.
    """
    if isinstance(base, RnsArray):
        base, x = base.base, base.x
    inv_t, m_col = _tables(base)
    flat, lead = _flatten_batch(x.astype(jnp.int32))
    xt, B = _pad_to(flat.T, block_b, axis=1)
    block_b = min(block_b, xt.shape[1])
    out = mrc_kernel_call(xt, inv_t, m_col, block_b=block_b, interpret=interpret)
    return out[:, :B].T.reshape(*lead, base.n).astype(x.dtype)


def modmul_op(base, x=None, y=None, *, block_b: int = 1024,
              interpret: bool | None = None):
    """Channel-wise (x * y) mod m_i via the Pallas kernel.

    Also callable as ``modmul_op(a, b)`` with two ``RnsArray`` operands of
    matching base/layout: the kernel then reduces EVERY channel in its own
    modulus (redundant rows included) and the result comes back typed.
    """
    arr = None
    if isinstance(base, RnsArray):
        arr, other = base, x
        if not isinstance(other, RnsArray):
            raise TypeError("modmul_op(a, b) needs both operands as RnsArray")
        other = arr._lift(other)  # validates matching base/layout/mb
        if arr.base.bits > 15:
            raise ValueError("Pallas kernels require bits<=15 (int32 lanes)")
        m_col = jnp.asarray(arr.channel_moduli[:, None], dtype=jnp.int32)
        x, y = arr.to_packed(), other.to_packed()
        nch = arr.n_channels
        base = arr.base
    else:
        _, m_col = _tables(base)
        nch = base.n
    fx, lead = _flatten_batch(x.astype(jnp.int32))
    fy, _ = _flatten_batch(y.astype(jnp.int32))
    xt, B = _pad_to(fx.T, block_b, axis=1)
    yt, _ = _pad_to(fy.T, block_b, axis=1)
    block_b = min(block_b, xt.shape[1])
    out = modmul_kernel_call(xt, yt, m_col, block_b=block_b, interpret=interpret)
    out = out[:, :B].T.reshape(*lead, nch).astype(x.dtype)
    if arr is not None:
        return RnsArray(
            out, base, layout=arr.layout,
            signed=arr.signed or other.signed, channel_axis=-1, mb=arr.mb,
        ).with_channel_axis(arr.channel_axis)
    return out


def compare_op(
    base, x1=None, xa1=None, x2=None, xa2=None, *, block_b: int = 512,
    interpret: bool | None = None
):
    """Fused Algorithm 1: boolean (N1 >= N2) for batched operands.

    x1, x2: (..., n); xa1, xa2: (...,).

    Also callable as ``compare_op(a, b)`` with two ``RnsArray`` operands
    (BASE_MA or RRNS layout — the m_a channel drives Theorem 1).
    """
    if isinstance(base, RnsArray):
        a, b = base, x1
        if not isinstance(b, RnsArray):
            raise TypeError("compare_op(a, b) needs both operands as "
                            "RnsArray")
        b = a._lift(b)  # validates matching base/layout/mb
        base, x1, xa1, x2, xa2 = a.base, a.x, a.xa, b.x, b.xa
    inv_t, m_col = _tables(base)
    betas_col = jnp.asarray(base.betas_ma_np[:, None], dtype=jnp.int32)
    f1, lead = _flatten_batch(x1.astype(jnp.int32))
    f2, _ = _flatten_batch(x2.astype(jnp.int32))
    a1 = xa1.astype(jnp.int32).reshape(1, -1)
    a2 = xa2.astype(jnp.int32).reshape(1, -1)
    x1t, B = _pad_to(f1.T, block_b, axis=1)
    x2t, _ = _pad_to(f2.T, block_b, axis=1)
    a1p, _ = _pad_to(a1, block_b, axis=1)
    a2p, _ = _pad_to(a2, block_b, axis=1)
    block_b = min(block_b, x1t.shape[1])
    out = compare_kernel_call(
        x1t, a1p, x2t, a2p, inv_t, m_col, betas_col,
        ma=base.ma, block_b=block_b, interpret=interpret,
    )
    return out[0, :B].reshape(lead).astype(bool)


def _auto_block(nelems: int, interpret: bool) -> int:
    """Default tile width: 1024 keeps compiled tiles VMEM-friendly on TPU;
    the interpreter has no VMEM and pays per grid step, so it takes the
    whole (padded) buffer as one tile."""
    return max(1, nelems) if interpret else 1024


def codec_decode_op(codec, summed, *, block_b: int | None = None,
                    interpret: bool | None = None,
                    channel_major: bool = False):
    """Fused gradient-codec decode: summed channels (..., nch) -> f32 mean
    gradient contribution (caller divides by world).  See codec_decode.py.
    Redundant channels beyond the base (m_a, and m_b on locate-and-correct
    codecs) ride along unread — the decode consumes base residues only.

    channel_major=True takes the kernel-native (nch, B) layout directly and
    returns (B,) — the zero-transpose path used by the bucketed pipeline.
    """
    from .codec_decode import codec_decode_kernel_call

    base = codec.base
    if base.M >= 1 << 45:
        raise ValueError("codec decode kernel requires M < 2**45 (3 limbs)")
    interpret = resolve_interpret(interpret)
    inv_t, m_col = _tables(base)
    T = (base.M + 1) // 2
    M = base.M
    half_col = jnp.asarray(
        [[T & 0x7FFF], [(T >> 15) & 0x7FFF], [T >> 30],
         [M & 0x7FFF], [(M >> 15) & 0x7FFF], [M >> 30]], dtype=jnp.int32,
    )
    if channel_major:
        flat_t, lead = summed.astype(jnp.int32), None
    else:
        flat, lead = _flatten_batch(summed.astype(jnp.int32))
        flat_t = flat.T
    if block_b is None:
        block_b = _auto_block(flat_t.shape[1], interpret)
    xt, B = _pad_to(flat_t, block_b, axis=1)
    block_b = min(block_b, xt.shape[1])
    out = codec_decode_kernel_call(
        xt, inv_t, m_col, half_col, n=base.n,
        inv_scale=1.0 / (1 << codec.frac_bits),
        block_b=block_b, interpret=interpret,
    )
    return out[0, :B] if channel_major else out[0, :B].reshape(lead)


def codec_encode_op(codec, g, *, block_b: int | None = None,
                    interpret: bool | None = None,
                    channel_major: bool = False):
    """Fused gradient-codec encode: f32 tensor (...,) -> packed int32
    residues (..., nch), bitwise identical to ``GradCodec.encode`` (which
    needs global x64; this kernel does not).  nch = n base channels plus the
    codec's redundant moduli (m_a alone, or m_a + m_b when the codec was
    built with ``correct=True``).  See codec_encode.py.

    channel_major=True returns the kernel-native (nch, B) layout for a
    flat (B,) input — the zero-transpose path used by the bucketed
    pipeline (the decode kernel consumes it directly).
    """
    from .codec_encode import codec_encode_kernel_call

    base = codec.base
    if base.M >= 1 << 45:
        raise ValueError("codec encode kernel requires M < 2**45 "
                         "(qmax limbs must fit 2x15-bit + int32 high part)")
    if base.bits > 15:
        raise ValueError("Pallas kernels require bits<=15 (int32 lanes); "
                         "use GradCodec.encode for wider bases")
    interpret = resolve_interpret(interpret)
    reds = codec.redundant  # (m_a,) or (m_a, m_b)
    m_all = jnp.asarray(
        np.concatenate([base.moduli_np, reds])[:, None], dtype=jnp.int32
    )
    pow15 = jnp.asarray(
        [[(1 << 15) % int(m)] for m in tuple(base.moduli) + reds],
        dtype=jnp.int32,
    )
    # negative-embedding shift per row: base rows 0 (m_i | M), redundant
    # rows M mod m_r
    off = jnp.asarray(
        [[0]] * base.n + [[base.M % r] for r in reds], dtype=jnp.int32
    )
    lead = g.shape if not channel_major else None
    row = g.astype(jnp.float32).reshape(1, -1)
    if block_b is None:
        block_b = _auto_block(row.shape[1], interpret)
    gt, B = _pad_to(row, block_b, axis=1)
    block_b = min(block_b, gt.shape[1])
    out = codec_encode_kernel_call(
        gt, m_all, pow15, off, scale=float(1 << codec.frac_bits),
        qh=codec.qmax >> 15, ql=codec.qmax & 0x7FFF,
        block_b=block_b, interpret=interpret,
    )
    if channel_major:
        return out[:, :B]
    return out[:, :B].T.reshape(*lead, len(m_all))


# ------------------------------------------------- Montgomery (dual-base)


@functools.lru_cache(maxsize=None)
def _mont_tables_np(baseB: RNSBase, baseBp: RNSBase,
                    lo_targets: tuple[int, ...]):
    """Host tables for the dual-base Montgomery kernels, cached per base
    pair + B-side channel layout (N-independent)."""
    from repro.core.montgomery import minv_residues

    for b in (baseB, baseBp):
        if b.bits > 15:
            raise ValueError("Pallas kernels require bits<=15 (int32 "
                             "lanes); use repro.core for wider bases")
    hi_t = tuple(int(m) for m in baseBp.moduli)
    return (
        np.asarray(baseB.inv_tri_np.T, np.int32),             # (n, n)
        np.asarray(lo_targets, np.int32)[:, None],            # (nch_lo, 1)
        np.asarray(baseB.betas_for(hi_t), np.int32),          # (n', n)
        np.asarray(baseBp.inv_tri_np.T, np.int32),            # (n', n')
        np.asarray(hi_t, np.int32)[:, None],                  # (n', 1)
        np.asarray(baseBp.betas_for(lo_targets), np.int32),   # (nch_lo, n')
        np.asarray(minv_residues(baseB, hi_t), np.int32)[:, None],
    )


def _mont_prep(d, lead, block_b):
    """DualRep -> padded channel-major (nch_lo, B) / (n_hi, B) tiles."""
    lo = jnp.broadcast_to(d.lo._cl().astype(jnp.int32),
                          (*lead, d.lo.n_channels))
    hi = jnp.broadcast_to(d.hi._cl().astype(jnp.int32),
                          (*lead, d.hi.base.n))
    lo_t, B = _pad_to(lo.reshape(-1, lo.shape[-1]).T, block_b, axis=1)
    hi_t, _ = _pad_to(hi.reshape(-1, hi.shape[-1]).T, block_b, axis=1)
    return lo_t, hi_t, B


def _mont_consts_prep(x, neg, n_hi, lead, block_b):
    neg = jnp.broadcast_to(jnp.asarray(neg, jnp.int32),
                           (*lead, x.lo.base.n))
    nhi = jnp.broadcast_to(jnp.asarray(n_hi, jnp.int32),
                           (*lead, x.hi.base.n))
    neg_t, _ = _pad_to(neg.reshape(-1, neg.shape[-1]).T, block_b, axis=1)
    nhi_t, _ = _pad_to(nhi.reshape(-1, nhi.shape[-1]).T, block_b, axis=1)
    return neg_t, nhi_t


def _mont_wrap(x, out_lo, out_hi, B, lead):
    from repro.core.montgomery import DualRep

    lo = out_lo[:, :B].T.reshape(*lead, -1).astype(x.lo.dtype)
    hi = out_hi[:, :B].T.reshape(*lead, -1).astype(x.hi.dtype)
    return DualRep(x.lo._wrap(lo, signed=False),
                   x.hi._wrap(hi, signed=False))


def mont_mul_op(x, y, neg, n_hi, *, block_b: int = 256,
                interpret: bool | None = None):
    """Batched Montgomery product MM(X, Y) via the fused Pallas kernel.

    ``x``/``y`` are ``DualRep`` operands (core/montgomery.py); ``neg`` /
    ``n_hi`` are the per-``N`` channel rows from ``mont_consts`` — arrays,
    not constants, broadcast against the batch.  Bitwise-identical to the
    pure-jnp ``_mont_mul_jnp`` reference.
    """
    lo_targets = tuple(int(m) for m in x.lo.channel_moduli)
    tables = [jnp.asarray(t) for t in
              _mont_tables_np(x.lo.base, x.hi.base, lo_targets)]
    lead = jnp.broadcast_shapes(x.lo.shape, y.lo.shape,
                                jnp.shape(neg)[:-1], jnp.shape(n_hi)[:-1])
    xlo, xhi, B = _mont_prep(x, lead, block_b)
    ylo, yhi, _ = _mont_prep(y, lead, block_b)
    neg_t, nhi_t = _mont_consts_prep(x, neg, n_hi, lead, block_b)
    block_b = min(block_b, xlo.shape[1])
    out_lo, out_hi = mont_mul_kernel_call(
        xlo, xhi, ylo, yhi, neg_t, nhi_t, *tables,
        block_b=block_b, interpret=interpret)
    return _mont_wrap(x, out_lo, out_hi, B, lead)


def mont_ladder_op(r0, r1, bit, neg, n_hi, *, block_b: int = 256,
                   interpret: bool | None = None):
    """One fused Montgomery-ladder bit: two products + branchless select
    in a single kernel launch.  Returns the updated ``(r0, r1)`` pair."""
    lo_targets = tuple(int(m) for m in r0.lo.channel_moduli)
    tables = [jnp.asarray(t) for t in
              _mont_tables_np(r0.lo.base, r0.hi.base, lo_targets)]
    lead = jnp.broadcast_shapes(r0.lo.shape, r1.lo.shape, jnp.shape(bit),
                                jnp.shape(neg)[:-1], jnp.shape(n_hi)[:-1])
    r0lo, r0hi, B = _mont_prep(r0, lead, block_b)
    r1lo, r1hi, _ = _mont_prep(r1, lead, block_b)
    neg_t, nhi_t = _mont_consts_prep(r0, neg, n_hi, lead, block_b)
    bit_b = jnp.broadcast_to(jnp.asarray(bit, jnp.int32), lead)
    bit_t, _ = _pad_to(bit_b.reshape(1, -1), block_b, axis=1)
    block_b = min(block_b, r0lo.shape[1])
    o0lo, o0hi, o1lo, o1hi = mont_ladder_kernel_call(
        r0lo, r0hi, r1lo, r1hi, bit_t, neg_t, nhi_t, *tables,
        block_b=block_b, interpret=interpret)
    return (_mont_wrap(r0, o0lo, o0hi, B, lead),
            _mont_wrap(r0, o1lo, o1hi, B, lead))
