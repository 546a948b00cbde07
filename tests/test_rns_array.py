"""RnsArray typed-frontend guarantees (core/array.py, DESIGN.md §11):

* every legacy entry point (rns_compare_ge, compare_packed_ge, divmod_rns,
  encode_signed, halve/scale_pow2, extend_mrc, GradCodec.encode) is
  BITWISE-identical to its RnsArray counterpart on randomized inputs —
  the shim contract that let the legacy tests survive the API redesign
  unmodified;
* RnsArray is a real pytree: jit / vmap / tree_map / flatten round-trips
  preserve both the buffer and the static aux;
* the backend context manager swaps implementations (jnp <-> Pallas
  kernels) without changing a single output bit.

Randomized with seeded numpy (no optional deps) — the hypothesis-based
exactness suites in test_core_rns.py cover the underlying algorithms.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    Layout,
    RnsArray,
    backend,
    compare_packed_ge,
    divmod_rns,
    encode_signed,
    extend_mrc,
    get_backend,
    halve,
    make_base,
    pack,
    rns_compare_ge,
    rns_to_int,
    scale_pow2,
)
from repro.dist.grad_codec import GradCodec, tree_pack, tree_pack_rns

BASE8 = make_base(4, bits=8)
BASE15 = make_base(6, bits=15)


def _rand_pairs(base, k, rng):
    draw = lambda: int.from_bytes(rng.bytes(16), "little") % base.M
    vals1 = [draw() for _ in range(k)]
    vals2 = [draw() for _ in range(k)]
    # adversarial edges: equal, adjacent, extremes
    vals1[:4] = [0, base.M - 1, base.M // 2, vals2[3]]
    vals2[:4] = [0, base.M - 1, base.M // 2 + 1, vals2[3]]
    return vals1, vals2


def _lift(base, vals):
    x = jnp.asarray(np.stack([base.residues_of(v) for v in vals]))
    xa = jnp.asarray(np.asarray([v % base.ma for v in vals], base.dtype))
    return x, xa


# ----------------------------------------------------- shim bitwise identity
@pytest.mark.parametrize("base", [BASE8, BASE15], ids=["8bit", "15bit"])
def test_compare_shims_bitwise(base):
    rng = np.random.default_rng(0)
    vals1, vals2 = _rand_pairs(base, 64, rng)
    x1, a1 = _lift(base, vals1)
    x2, a2 = _lift(base, vals2)
    truth = np.asarray(vals1) >= np.asarray(vals2)

    legacy = np.asarray(rns_compare_ge(base, x1, a1, x2, a2))
    legacy_packed = np.asarray(
        compare_packed_ge(base, pack(base, x1, a1), pack(base, x2, a2))
    )
    arr1 = RnsArray.from_parts(base, x1, a1)
    arr2 = RnsArray.from_parts(base, x2, a2)
    typed = np.asarray(arr1.compare_ge(arr2))
    op = np.asarray(arr1 >= arr2)

    np.testing.assert_array_equal(legacy, truth)
    np.testing.assert_array_equal(legacy_packed, truth)
    np.testing.assert_array_equal(typed, truth)
    np.testing.assert_array_equal(op, truth)
    # strict/reversed operators agree with exact semantics
    np.testing.assert_array_equal(
        np.asarray(arr1 < arr2), ~truth
    )
    np.testing.assert_array_equal(
        np.asarray(arr1 > arr2), np.asarray(vals1) > np.asarray(vals2)
    )


def test_divmod_shim_bitwise():
    base = make_base(3, bits=8)
    rng = np.random.default_rng(1)
    X = [int(rng.integers(0, base.M)) for _ in range(8)]
    D = [max(1, int(rng.integers(1, base.M))) for _ in range(8)]
    xp = pack(base, *_lift(base, X))
    dp = pack(base, *_lift(base, D))

    q_legacy, r_legacy = divmod_rns(base, xp, dp)
    q, r = RnsArray.from_packed(base, xp).divmod(
        RnsArray.from_packed(base, dp)
    )
    np.testing.assert_array_equal(np.asarray(q_legacy),
                                  np.asarray(q.to_packed()))
    np.testing.assert_array_equal(np.asarray(r_legacy),
                                  np.asarray(r.to_packed()))
    for i in range(8):
        assert (
            rns_to_int(base, np.asarray(q.x[i])),
            rns_to_int(base, np.asarray(r.x[i])),
        ) == divmod(X[i], D[i])


def test_encode_signed_shim_bitwise():
    base = make_base(3, bits=15)
    rng = np.random.default_rng(2)
    bound = (base.M - 1) // 2
    v = jnp.asarray(rng.integers(-bound, bound, size=64, dtype=np.int64))
    legacy = np.asarray(encode_signed(base, v))
    arr = RnsArray.encode_signed(base, v)
    np.testing.assert_array_equal(legacy, np.asarray(arr.to_packed()))
    assert arr.signed and arr.layout is Layout.BASE_MA
    np.testing.assert_array_equal(np.asarray(arr.to_int()), np.asarray(v))
    np.testing.assert_array_equal(
        np.asarray(arr.is_negative()), np.asarray(v) < 0
    )


def test_halve_scale_extend_shims_bitwise():
    base = BASE8
    rng = np.random.default_rng(3)
    vals = [int(rng.integers(0, base.M)) for _ in range(16)]
    packed = pack(base, *_lift(base, vals))
    arr = RnsArray.from_packed(base, packed)

    np.testing.assert_array_equal(
        np.asarray(halve(base, packed)),
        np.asarray(arr.halve().to_packed()),
    )
    np.testing.assert_array_equal(
        np.asarray(scale_pow2(base, packed, 3)),
        np.asarray(arr.scale_pow2(3).to_packed()),
    )
    assert arr.scale_pow2(3).to_int().tolist() == [v // 8 for v in vals]
    targets = (251, 241)
    np.testing.assert_array_equal(
        np.asarray(extend_mrc(base, arr.x, targets)),
        np.asarray(arr.extend(targets)),
    )


@pytest.mark.parametrize("correct", [False, True], ids=["detect", "rrns"])
def test_grad_codec_encode_bitwise(correct):
    codec = GradCodec.make(world=4, correct=correct)
    rng = np.random.default_rng(4)
    g = jnp.asarray(rng.standard_normal(128).astype(np.float32))

    raw = codec.encode(g)
    arr = codec.encode_array(g)
    assert arr.layout is codec.layout
    assert arr.signed and arr.mb == codec.mb
    np.testing.assert_array_equal(np.asarray(raw), np.asarray(arr.to_packed()))

    wire = codec.encode_array(g, channel_major=True)
    assert wire.channel_axis == 0
    np.testing.assert_array_equal(
        np.asarray(codec.encode_packed(g, channel_major=True)),
        np.asarray(wire.residues),
    )
    # typed fold/normalize return in kind and match the raw path bitwise
    folded = codec.fold(arr)
    assert isinstance(folded, RnsArray)
    np.testing.assert_array_equal(
        np.asarray(codec.fold(raw)), np.asarray(folded.to_packed())
    )
    norm = codec.normalize(folded)
    np.testing.assert_array_equal(
        np.asarray(codec.normalize(codec.fold(raw))),
        np.asarray(norm.to_packed()),
    )


def test_grad_codec_correct_typed_wire():
    codec = GradCodec.make(world=2, correct=True)
    rng = np.random.default_rng(5)
    g = jnp.asarray(rng.standard_normal(32).astype(np.float32))
    wire = codec.encode_array(g, channel_major=True)
    m0 = int(codec.base.moduli[0])
    bad = type(wire).tree_unflatten(
        wire.tree_flatten()[1],
        (wire.residues.at[0, 3].set(jnp.mod(wire.residues[0, 3] + 5, m0)),),
    )
    fixed, fault = codec.correct_packed(bad)
    assert isinstance(fixed, RnsArray) and fixed.channel_axis == 0
    assert int(fault[3]) == 0 and int(jnp.sum(fault >= 0)) == 1
    np.testing.assert_array_equal(
        np.asarray(fixed.residues), np.asarray(wire.residues)
    )
    # raw path agrees bitwise
    fixed_raw, fault_raw = codec.correct_packed(bad.to_packed())
    np.testing.assert_array_equal(
        np.asarray(fixed_raw), np.asarray(fixed.to_packed())
    )
    np.testing.assert_array_equal(np.asarray(fault_raw), np.asarray(fault))


def test_tree_pack_rns_matches_raw():
    codec = GradCodec.make(world=2)
    rng = np.random.default_rng(6)
    tree = {
        "a": jnp.asarray(rng.standard_normal((4, 3)).astype(np.float32)),
        "b": jnp.asarray(rng.standard_normal(7).astype(np.float32)),
    }
    buf, meta = tree_pack(codec, tree)
    arr, meta2 = tree_pack_rns(codec, tree)
    assert isinstance(arr, RnsArray) and arr.channel_axis == 0
    np.testing.assert_array_equal(np.asarray(buf), np.asarray(arr.residues))
    assert meta.shapes == meta2.shapes and meta.dtypes == meta2.dtypes


# ------------------------------------------------------------ pytree-ness
def test_pytree_roundtrip_jit_vmap_treemap():
    base = BASE8
    a = RnsArray.encode(base, jnp.asarray([[5, 9], [100, 2]]))

    leaves, treedef = jax.tree_util.tree_flatten(a)
    assert len(leaves) == 1
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.base == base and back.layout is a.layout
    np.testing.assert_array_equal(np.asarray(back.residues),
                                  np.asarray(a.residues))

    # jit: static aux survives, values untouched, arithmetic traces
    f = jax.jit(lambda u, v: u + v)
    s = f(a, a)
    assert isinstance(s, RnsArray) and s.layout is Layout.BASE_MA
    assert s.to_int().tolist() == [[10, 18], [200, 4]]

    # vmap over the leading batch axis
    digits = jax.vmap(lambda u: u.to_mrs())(a)
    assert digits.shape == (2, 2, base.n)

    # tree_map sees exactly one leaf
    shapes = jax.tree_util.tree_map(lambda x: x.shape, a)
    assert shapes.residues == (2, 2, a.n_channels)


def test_pytree_psum_single_collective():
    """An RnsArray flows through lax.psum as ONE leaf — the bucketed
    transport's single-collective guarantee survives the typed wire."""
    from jax.sharding import Mesh, PartitionSpec as P

    codec = GradCodec.make(world=max(len(jax.devices()), 2))
    mesh = Mesh(np.array(jax.devices()), ("data",))
    g = jnp.ones((8,), jnp.float32)

    def step(x):
        arr = codec.encode_array(x, channel_major=True)
        return jax.lax.psum(arr, "data")

    jaxpr = jax.make_jaxpr(
        jax.shard_map(step, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)
    )(g)
    assert str(jaxpr).count("psum") == 1


def test_constructor_validation():
    base = BASE8
    with pytest.raises(ValueError):  # RRNS needs mb
        RnsArray(jnp.zeros((3, base.n + 2), jnp.int32), base,
                 layout=Layout.RRNS)
    with pytest.raises(ValueError):  # channel count mismatch
        RnsArray(jnp.zeros((3, base.n + 1), jnp.int32), base,
                 layout=Layout.BASE)
    with pytest.raises(ValueError):  # BASE layout cannot compare
        a = RnsArray.encode(base, jnp.asarray([1]), layout=Layout.BASE)
        _ = a >= a
    with pytest.raises(ValueError):  # unsigned arrays have no sign
        RnsArray.encode(base, jnp.asarray([1])).is_negative()
    arr = RnsArray.encode(base, jnp.asarray([7, 8]))
    wire = arr.with_channel_axis(0)
    assert wire.residues.shape == (arr.n_channels, 2)
    np.testing.assert_array_equal(
        np.asarray(wire.with_channel_axis(-1).residues),
        np.asarray(arr.residues),
    )


def test_signed_halve_rejected_and_operand_protocol():
    base = BASE8
    s = RnsArray.encode_signed(base, jnp.asarray([-7]))
    with pytest.raises(ValueError):  # floor(X/2) is wrong for negative v
        s.halve()
    with pytest.raises(ValueError):
        s.scale_pow2(2)
    a = RnsArray.encode(base, jnp.asarray([5]))
    with pytest.raises(TypeError):  # NotImplemented propagates, not AttrError
        _ = a <= "foo"
    with pytest.raises(TypeError):
        _ = a > object()
    with pytest.raises(TypeError):
        _ = a >= 1.5
    with pytest.raises(TypeError):
        _ = a < None
    # typed kernel entry points validate operands like the operators do
    from repro.kernels import compare_op, modmul_op

    other = RnsArray.encode(make_base(4, bits=9), jnp.asarray([5]))
    with pytest.raises(ValueError):
        modmul_op(a, other)
    with pytest.raises(ValueError):
        compare_op(a, other)
    with pytest.raises(ValueError):  # too FEW channels is a clear error
        RnsArray.from_packed(base, jnp.zeros((2, base.n - 1), jnp.int32))


def test_mixed_layout_and_base_rejected():
    a = RnsArray.encode(BASE8, jnp.asarray([1]))
    b = RnsArray.encode(BASE8, jnp.asarray([1]), layout=Layout.BASE)
    with pytest.raises(ValueError):
        _ = a + b
    c = RnsArray.encode(make_base(3, bits=8), jnp.asarray([1]))
    with pytest.raises(ValueError):
        _ = a + c


# ------------------------------------------------------------ backend knob
def test_backend_context_bitwise_and_restores():
    base = BASE15
    rng = np.random.default_rng(7)
    vals1, vals2 = _rand_pairs(base, 32, rng)
    a = RnsArray.from_parts(base, *_lift(base, vals1))
    b = RnsArray.from_parts(base, *_lift(base, vals2))

    assert get_backend() == "auto"
    with backend("jnp"):
        ge_jnp = np.asarray(a >= b)
        mul_jnp = np.asarray((a * b).residues)
        mrs_jnp = np.asarray(a.to_mrs())
    with backend("pallas"):
        assert get_backend() == "pallas"
        ge_pl = np.asarray(a >= b)
        mul_pl = np.asarray((a * b).residues)
        mrs_pl = np.asarray(a.to_mrs())
    assert get_backend() == "auto"

    np.testing.assert_array_equal(ge_jnp, ge_pl)
    np.testing.assert_array_equal(mul_jnp, mul_pl)
    np.testing.assert_array_equal(mrs_jnp, mrs_pl)
    np.testing.assert_array_equal(
        ge_jnp, np.asarray(vals1) >= np.asarray(vals2)
    )

    with pytest.raises(ValueError):
        with backend("cuda"):
            pass


def test_backend_overrides_codec_fused():
    codec = GradCodec.make(world=2)           # qualifies for the kernels
    assert codec.use_fused                    # auto: fused on
    with backend("jnp"):
        assert not codec.use_fused            # forced reference path
    unfused = GradCodec.make(world=2, fused=False)
    with backend("pallas"):
        assert unfused.use_fused              # forced kernels
    g = jnp.asarray(np.random.default_rng(8)
                    .standard_normal(32).astype(np.float32))
    with backend("jnp"):
        ref = np.asarray(codec.encode_packed(g))
    with backend("pallas"):
        fused = np.asarray(codec.encode_packed(g))
    np.testing.assert_array_equal(ref, fused)  # bitwise across backends
