"""Fused single-buffer RNS all-reduce pipeline (DESIGN.md §9).

Tier-1 coverage (no optional deps): the fused Pallas encode/decode kernels
must be BITWISE identical to the jnp codec path on the tier-1 base (n=3,
bits=15), the bucketed ``rns_psum_tree`` must issue exactly ONE per-channel
psum for a multi-leaf pytree, and every fallback/guard rail must hold
(block padding, dynamic-range corners, M >= 2**45 rejection, x64 guard).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.grad_codec import (
    GradCodec,
    rns_psum,
    rns_psum_tree,
    tree_decode,
    tree_pack,
)
from repro.kernels import codec_decode_op, codec_encode_op


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _adversarial_grads(codec, rng, n=2048):
    """Normal mass plus every clip/sign corner the encode must get right."""
    return jnp.asarray(np.concatenate([
        rng.standard_normal(n).astype(np.float32),
        (rng.standard_normal(64) * 1e7).astype(np.float32),  # clips at qmax
        np.asarray([0.0, -0.0, 1e-9, -1e-9, np.inf, -np.inf,
                    codec.clip, -codec.clip,
                    np.nextafter(np.float32(codec.clip), np.float32(np.inf)),
                    -np.nextafter(np.float32(codec.clip), np.float32(np.inf))],
                   np.float32),
    ]))


# ------------------------------------------------------------ fused encode
@pytest.mark.parametrize("world", [2, 512])
def test_encode_kernel_bitwise_vs_jnp(world):
    codec = GradCodec.make(world=world)  # tier-1 base: n=3, bits=15
    g = _adversarial_grads(codec, np.random.default_rng(world))
    want = np.asarray(codec.encode(g))
    got = np.asarray(codec_encode_op(codec, g, interpret=True))
    np.testing.assert_array_equal(got, want)


def test_encode_kernel_block_padding_and_layout():
    codec = GradCodec.make(world=8)
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(301).astype(np.float32))  # 301 % 128
    want = np.asarray(codec.encode(g))
    got = np.asarray(codec_encode_op(codec, g, block_b=128, interpret=True))
    np.testing.assert_array_equal(got, want)
    major = np.asarray(
        codec_encode_op(codec, g, block_b=128, interpret=True,
                        channel_major=True)
    )
    assert major.shape == (codec.base.n + 1, 301)
    np.testing.assert_array_equal(major.T, want)
    # leading batch dims round-trip through the (..., n+1) layout
    g2 = g[:300].reshape(4, 75)
    np.testing.assert_array_equal(
        np.asarray(codec_encode_op(codec, g2, block_b=64, interpret=True)),
        np.asarray(codec.encode(g2)),
    )


# ------------------------------------------------------------ fused decode
def _summed_for(codec, q):
    """Emulate the post-psum channel sums of integer values ``q``."""
    from repro.core.convert import tensor_to_rns

    q = jnp.asarray(q, jnp.int64)
    res = tensor_to_rns(codec.base, q)
    xa = jnp.mod(q, codec.base.ma)
    xa = jnp.where(q < 0, jnp.mod(xa + codec.base.M_mod_ma, codec.base.ma), xa)
    return jnp.concatenate(
        [res.astype(jnp.int32), xa[..., None].astype(jnp.int32)], axis=-1
    )


def test_decode_kernel_bitwise_vs_jnp():
    codec = GradCodec.make(world=64)
    rng = np.random.default_rng(1)
    gs = rng.standard_normal((64, 700)).astype(np.float32)
    packs = np.stack([np.asarray(codec.encode(jnp.asarray(r))) for r in gs])
    summed = jnp.asarray(packs.sum(0).astype(np.int32))
    want = np.asarray(codec.decode(codec.fold(summed)))
    got = np.asarray(codec_decode_op(codec, summed, interpret=True))
    np.testing.assert_array_equal(got, want)


def test_decode_kernel_block_padding_edge():
    """Batch not a multiple of block_b exercises the padding path."""
    codec = GradCodec.make(world=16)
    rng = np.random.default_rng(2)
    for batch in (1, 7, 129, 300):
        q = rng.integers(-codec.qmax, codec.qmax, size=batch) * 16
        summed = _summed_for(codec, q)
        want = np.asarray(codec.decode(codec.fold(summed)))
        got = np.asarray(
            codec_decode_op(codec, summed, block_b=128, interpret=True)
        )
        np.testing.assert_array_equal(got, want)


def test_decode_kernel_extreme_negative_sums():
    """Maximally negative sums at qmax * world: the dynamic-range corner
    where the signed fold's borrow chain and the f32 cast both peak."""
    codec = GradCodec.make(world=512)
    corners = np.asarray(
        [-codec.qmax, codec.qmax, -codec.qmax + 1, -1, 0, 1], np.int64
    ) * 512
    summed = _summed_for(codec, corners)
    want = np.asarray(codec.decode(codec.fold(summed)))
    got = np.asarray(codec_decode_op(codec, summed, block_b=8, interpret=True))
    np.testing.assert_array_equal(got, want)
    # the most-negative value really decodes negative and at full magnitude
    assert got[0] == -float(codec.qmax * 512) * 2.0 ** -codec.frac_bits


def test_kernels_reject_wide_dynamic_range():
    """M >= 2**45 breaks the 3-limb discipline: both ops refuse, and the
    codec-level dispatch falls back to the jnp path instead of calling them."""
    codec = GradCodec.make(world=2, n=4)  # M ~ 2**60
    assert codec.base.M >= 1 << 45 and not codec.use_fused
    g = jnp.ones((8,), jnp.float32)
    with pytest.raises(ValueError, match="2\\*\\*45"):
        codec_encode_op(codec, g, interpret=True)
    with pytest.raises(ValueError, match="2\\*\\*45"):
        codec_decode_op(codec, jnp.ones((8, 5), jnp.int32), interpret=True)
    # fallback: encode_packed/decode_summed still work (jnp path)
    packed = codec.encode_packed(g)
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(codec.encode(g)))
    dec = codec.decode_summed(packed.astype(jnp.int32))
    np.testing.assert_allclose(np.asarray(dec), np.ones(8), atol=1e-4)
    # channel_major fallback must match the kernel's flatten-then-transpose
    # layout even for non-1D input (not an axis-reversed .T)
    g2 = jnp.asarray(
        np.random.default_rng(9).standard_normal((3, 4)).astype(np.float32)
    )
    major = codec.encode_packed(g2, channel_major=True)
    assert major.shape == (codec.base.n + 1, 12)
    np.testing.assert_array_equal(
        np.asarray(major), np.asarray(codec.encode(jnp.ravel(g2))).T
    )


def test_encode_requires_x64():
    """GradCodec.encode silently mis-quantizes without global x64; it must
    refuse loudly instead (regression for the silent-degradation bug)."""
    codec = GradCodec.make(world=2)
    g = jnp.ones((4,), jnp.float32)
    assert codec.encode(g) is not None  # x64 on (repro import): fine
    jax.config.update("jax_enable_x64", False)
    try:
        with pytest.raises(RuntimeError, match="x64"):
            codec.encode(g)
    finally:
        jax.config.update("jax_enable_x64", True)


# --------------------------------------------------------- bucketed psum
def _count_collectives(jaxpr, name="psum"):
    """Recursively count ``name`` primitives across nested (closed) jaxprs."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for v in eqn.params.values():
            for vv in v if isinstance(v, (list, tuple)) else [v]:
                core = getattr(vv, "jaxpr", None)
                if hasattr(core, "eqns"):        # ClosedJaxpr
                    n += _count_collectives(core, name)
                elif hasattr(vv, "eqns"):        # bare Jaxpr
                    n += _count_collectives(vv, name)
    return n


def _grad_tree(rng):
    return {
        "wq": jnp.asarray(rng.standard_normal((16, 4)).astype(np.float32)),
        "mlp": [
            jnp.asarray(rng.standard_normal(300).astype(np.float32)),
            jnp.asarray(rng.standard_normal((2, 3, 5)).astype(np.float32)),
        ],
        "scale": jnp.asarray(rng.standard_normal((7,)).astype(np.float32)),
    }


def test_rns_psum_tree_single_collective():
    """The bucketing claim itself: a 4-leaf pytree moves in EXACTLY one
    psum, where the per-leaf path pays one per leaf."""
    codec = GradCodec.make(world=4)
    mesh = _mesh1()
    tree = _grad_tree(np.random.default_rng(3))
    bucketed = jax.make_jaxpr(jax.shard_map(
        lambda t: rns_psum_tree(codec, t, "data"), mesh=mesh,
        in_specs=(P(),), out_specs=P(), check_vma=False))(tree)
    per_leaf = jax.make_jaxpr(jax.shard_map(
        lambda t: jax.tree_util.tree_map(
            lambda g: rns_psum(codec, g, "data"), t),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False))(tree)
    assert _count_collectives(bucketed.jaxpr) == 1
    assert _count_collectives(per_leaf.jaxpr) == len(
        jax.tree_util.tree_leaves(tree)
    )


@pytest.mark.parametrize("fused", [True, False])
def test_rns_psum_tree_matches_per_leaf_bitwise(fused):
    codec = GradCodec.make(world=4, fused=fused)
    mesh = _mesh1()
    tree = _grad_tree(np.random.default_rng(4))
    out = jax.jit(jax.shard_map(lambda t: rns_psum_tree(codec, t, "data"),
                                mesh=mesh, in_specs=(P(),), out_specs=P(),
                                check_vma=False))(tree)
    ref = jax.jit(jax.shard_map(
        lambda t: jax.tree_util.tree_map(
            lambda g: rns_psum(codec, g, "data"), t),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False))(tree)
    assert jax.tree_util.tree_structure(out) == \
        jax.tree_util.tree_structure(tree)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rns_psum_tree_fused_equals_unfused_bitwise():
    """The acceptance bar: fused and jnp transport agree BIT FOR BIT on the
    tier-1 base (n=3, bits=15) — encode residues and decoded f32 alike."""
    fused = GradCodec.make(world=4, fused=True)
    plain = GradCodec.make(world=4, fused=False)
    assert fused.use_fused and not plain.use_fused
    rng = np.random.default_rng(5)
    g = _adversarial_grads(fused, rng, n=500)
    tree = {"a": g, "b": g[:37].reshape(37, 1) * 3.0}
    mesh = _mesh1()
    run = lambda c: jax.jit(jax.shard_map(
        lambda t: rns_psum_tree(c, t, "data"), mesh=mesh,
        in_specs=(P(),), out_specs=P(), check_vma=False))(tree)
    a, b = run(fused), run(plain)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_tree_pack_layout_and_dtype_restore():
    codec = GradCodec.make(world=2)
    rng = np.random.default_rng(6)
    tree = {
        "f32": jnp.asarray(rng.standard_normal((3, 4)).astype(np.float32)),
        "bf16": jnp.asarray(
            rng.standard_normal(10).astype(np.float32)
        ).astype(jnp.bfloat16),
    }
    buf, meta = tree_pack(codec, tree)
    assert buf.shape == (codec.base.n + 1, 22) and buf.dtype == jnp.int32
    out = tree_decode(codec, buf, meta, denom=1.0)
    assert out["bf16"].dtype == jnp.bfloat16
    assert out["f32"].shape == (3, 4)
    np.testing.assert_allclose(
        np.asarray(out["f32"]), np.asarray(tree["f32"]),
        atol=2.0 ** -codec.frac_bits,
    )
    with pytest.raises(ValueError, match="empty"):
        tree_pack(codec, {})


# ------------------------------------------------------ optimizer boundary
def test_adamw_grad_decode_hook_equivalent():
    """Decoding inside adamw_update (the codec seam) must be exactly the
    same update as decoding before the call."""
    from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update

    codec = GradCodec.make(world=2)
    cfg = AdamWConfig()
    rng = np.random.default_rng(8)
    params = {"w": jnp.asarray(rng.standard_normal((4, 3)).astype(np.float32))}
    grads = {"w": jnp.asarray(rng.standard_normal((4, 3)).astype(np.float32))}
    buf, meta = tree_pack(codec, grads)
    summed = buf  # world-of-one psum
    decoded = tree_decode(codec, summed, meta)
    p_ref, s_ref, g_ref = adamw_update(
        cfg, params, decoded, adamw_init(params)
    )
    p_hook, s_hook, g_hook = adamw_update(
        cfg, params, summed, adamw_init(params),
        grad_decode=lambda s: tree_decode(codec, s, meta),
    )
    assert float(g_ref) == float(g_hook)
    np.testing.assert_array_equal(np.asarray(p_ref["w"]),
                                  np.asarray(p_hook["w"]))
    np.testing.assert_array_equal(np.asarray(s_ref["m"]["w"]),
                                  np.asarray(s_hook["m"]["w"]))


def test_train_step_rns_codec_smoke():
    """make_train_step(rns_codec=...) under shard_map: runs, returns finite
    metrics, and the fused/unfused variants agree bitwise on params."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.train.data import SyntheticLM
    from repro.train.optimizer import AdamWConfig, adamw_init
    from repro.train.train_step import make_train_step

    cfg = get_config("mamba2-370m").smoke()
    opt_cfg = AdamWConfig(warmup=2, decay_steps=4)
    params = init_params(cfg, jax.random.key(0))
    batch = jax.tree_util.tree_map(
        jnp.asarray, SyntheticLM(cfg, seq=16, batch=2).batch_at(0)
    )
    mesh = _mesh1()

    outs = {}
    for fused in (True, False):
        codec = GradCodec.make(world=2, fused=fused)
        step = make_train_step(cfg, opt_cfg, rns_codec=codec,
                               dp_axis="data")
        fn = jax.jit(jax.shard_map(step, mesh=mesh,
                                   in_specs=(P(), P(), P("data")),
                                   out_specs=(P(), P(), P()),
                                   check_vma=False))
        p2, _, metrics = fn(params, adamw_init(params), batch)
        assert np.isfinite(float(metrics["loss"]))
        assert np.isfinite(float(metrics["gnorm"]))
        outs[fused] = p2
    for a, b in zip(jax.tree_util.tree_leaves(outs[True]),
                    jax.tree_util.tree_leaves(outs[False])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
