"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle.

Integer kernels must match the oracle EXACTLY (assert_array_equal — stricter
than allclose).  Sweeps shapes (including non-multiples of the block size),
channel counts, moduli bit-widths, and input dtypes.
"""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed — pip install -r requirements-dev.txt",
)
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax.numpy as jnp

from repro.core import make_base
from repro.kernels import (
    compare_op,
    modmul_op,
    mrc_op,
    ref_compare,
    ref_modmul,
    ref_mrc,
)

NS = [2, 3, 6, 17]
BATCHES = [1, 7, 128, 300]
BITS = [8, 13, 15]


def _rand_residues(base, shape, rng):
    m = np.asarray(base.moduli_np)
    return rng.integers(0, m, size=shape + (base.n,)).astype(np.int32)


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("batch", BATCHES)
def test_mrc_kernel_matches_oracle(n, batch):
    base = make_base(n, bits=15)
    rng = np.random.default_rng(n * 1000 + batch)
    x = jnp.asarray(_rand_residues(base, (batch,), rng))
    got = mrc_op(base, x, block_b=128, interpret=True)
    want = ref_mrc(base, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bits", BITS)
def test_mrc_kernel_bit_widths(bits):
    base = make_base(5, bits=bits)
    rng = np.random.default_rng(bits)
    x = jnp.asarray(_rand_residues(base, (64,), rng))
    got = mrc_op(base, x, block_b=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref_mrc(base, x)))


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_mrc_kernel_dtypes(dtype):
    base = make_base(4, bits=15)
    rng = np.random.default_rng(0)
    x = jnp.asarray(_rand_residues(base, (32,), rng).astype(dtype))
    got = mrc_op(base, x, block_b=32, interpret=True)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref_mrc(base, x)))


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("batch", BATCHES)
def test_modmul_kernel_matches_oracle(n, batch):
    base = make_base(n, bits=15)
    rng = np.random.default_rng(n + batch)
    x = jnp.asarray(_rand_residues(base, (batch,), rng))
    y = jnp.asarray(_rand_residues(base, (batch,), rng))
    got = modmul_op(base, x, y, block_b=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref_modmul(base, x, y)))


def test_modmul_kernel_worst_case_products():
    """Largest residues: exercises the Barrett correction branches."""
    base = make_base(8, bits=15)
    m = np.asarray(base.moduli_np)
    x = jnp.asarray(np.broadcast_to(m - 1, (256, base.n)).astype(np.int32))
    got = modmul_op(base, x, x, block_b=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref_modmul(base, x, x)))


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("batch", BATCHES)
def test_compare_kernel_matches_oracle(n, batch):
    base = make_base(n, bits=15)
    rng = np.random.default_rng(7 * n + batch)
    x1 = jnp.asarray(_rand_residues(base, (batch,), rng))
    x2 = jnp.asarray(_rand_residues(base, (batch,), rng))
    # NOTE: random residue vectors are valid numbers in [0, M) by CRT, and
    # their m_a channels must be consistent — derive them exactly.
    from repro.core import rns_to_int

    a1 = jnp.asarray(
        np.asarray([rns_to_int(base, r) % base.ma for r in np.asarray(x1)], np.int32)
    )
    a2 = jnp.asarray(
        np.asarray([rns_to_int(base, r) % base.ma for r in np.asarray(x2)], np.int32)
    )
    got = compare_op(base, x1, a1, x2, a2, block_b=128, interpret=True)
    want = ref_compare(base, x1, a1, x2, a2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_compare_kernel_is_true_comparison(data):
    """End-to-end property: kernel verdict == integer >= (Theorem 1)."""
    base = make_base(4, bits=15)
    N1 = data.draw(st.integers(0, base.M - 1))
    N2 = data.draw(st.integers(0, base.M - 1))
    x1 = jnp.asarray(base.residues_of(N1)[None])
    x2 = jnp.asarray(base.residues_of(N2)[None])
    a1 = jnp.asarray([N1 % base.ma], dtype=jnp.int32)
    a2 = jnp.asarray([N2 % base.ma], dtype=jnp.int32)
    got = bool(compare_op(base, x1, a1, x2, a2, block_b=8, interpret=True)[0])
    assert got == (N1 >= N2)


def test_kernels_reject_wide_bases():
    base = make_base(3, bits=31)
    x = jnp.zeros((4, 3), dtype=jnp.int64)
    with pytest.raises(ValueError):
        mrc_op(base, x, interpret=True)


def test_codec_decode_kernel_matches_oracle():
    """Fused fold->MRC->Horner->sign->scale kernel vs the jnp codec path."""
    from repro.dist.grad_codec import GradCodec
    from repro.kernels import codec_decode_op

    codec = GradCodec.make(world=512)
    rng = np.random.default_rng(11)
    W = 64
    g = rng.standard_normal((W, 300)).astype(np.float32)
    packs = np.stack([np.asarray(codec.encode(jnp.asarray(r))) for r in g])
    summed = jnp.asarray(packs.sum(axis=0))          # what psum produces
    want = np.asarray(codec.decode(codec.fold(summed)))
    got = np.asarray(codec_decode_op(codec, summed, block_b=128,
                                     interpret=True))
    # the compensated limb sum makes the fused decode correctly rounded —
    # bitwise equal to the jnp f64 path, not merely close
    np.testing.assert_array_equal(got, want)


def test_codec_decode_kernel_extreme_values():
    from repro.dist.grad_codec import GradCodec
    from repro.kernels import codec_decode_op

    codec = GradCodec.make(world=512)
    # +-qmax summed over 512 replicas: the dynamic-range corners
    q = np.asarray([codec.qmax, -codec.qmax, 0, 1, -1], np.int64) * 512
    # encode clips per replica; emulate the summed corners directly:
    from repro.core.convert import tensor_to_rns
    res = tensor_to_rns(codec.base, jnp.asarray(q))
    xa = jnp.mod(jnp.asarray(q), codec.base.ma)
    xa = jnp.where(jnp.asarray(q) < 0,
                   jnp.mod(xa + codec.base.M_mod_ma, codec.base.ma), xa)
    summed = jnp.concatenate([res.astype(jnp.int32),
                              xa[..., None].astype(jnp.int32)], axis=-1)
    want = np.asarray(codec.decode(codec.fold(summed)))
    got = np.asarray(codec_decode_op(codec, summed, block_b=8, interpret=True))
    np.testing.assert_array_equal(got, want)


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_codec_encode_kernel_property(data):
    """Property: fused encode == jnp f64 encode BITWISE for arbitrary f32
    inputs (quantize, clip at qmax, signed embedding, redundant channel)."""
    from repro.dist.grad_codec import GradCodec
    from repro.kernels import codec_encode_op

    codec = GradCodec.make(world=data.draw(st.sampled_from([2, 32, 512])))
    big = float(np.float32(1e30))  # width=32 bounds must be f32-exact
    vals = data.draw(st.lists(
        st.floats(-big, big, width=32), min_size=1, max_size=64,
    ))
    g = jnp.asarray(np.asarray(vals, np.float32))
    np.testing.assert_array_equal(
        np.asarray(codec_encode_op(codec, g, block_b=32, interpret=True)),
        np.asarray(codec.encode(g)),
    )
