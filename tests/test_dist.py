"""Dist-subsystem tests: activation constraints, spec trees, the RNS
gradient codec round trip, and fingerprint-verified checkpoint restore.

These run with the base dependencies only (no hypothesis), so the dist layer
keeps tier-1 coverage even where optional dev deps are absent.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.dist.act_sharding import constrain, current_mesh, use_mesh
from repro.dist.fault import (
    find_restorable,
    tensor_fingerprint,
    tree_fingerprints,
    verify_fingerprints,
)
from repro.dist.grad_codec import GradCodec, rns_psum
from repro.dist.sharding import (
    batch_specs,
    cache_specs,
    named_shardings,
    opt_state_specs,
    param_specs,
)
from repro.models import abstract_params


def _mesh2d():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


class _FakeMesh:
    """Spec builders only consume .shape / .axis_names — this lets a 1-CPU
    host exercise the divisibility logic of a (data=4, model=8) mesh."""

    def __init__(self, **shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


# --------------------------------------------------------------- constrain
def test_constrain_noop_off_mesh():
    x = jnp.arange(12.0).reshape(3, 4)
    assert current_mesh() is None
    y = constrain(x, "batch", "ff")
    assert y is x  # literally untouched: no constraint op inserted


def test_use_mesh_installs_and_restores():
    mesh = _mesh2d()
    with use_mesh(mesh) as m:
        assert current_mesh() is mesh and m is mesh
        with use_mesh(None):
            assert current_mesh() is None
        assert current_mesh() is mesh
    assert current_mesh() is None


def test_constrain_on_mesh_preserves_values():
    mesh = _mesh2d()
    x = jnp.arange(16.0).reshape(4, 4)
    with mesh, use_mesh(mesh):
        y = jax.jit(lambda a: constrain(a, "batch", "ff"))(x)
        z = jax.jit(
            lambda a: constrain(a.reshape(2, 2, 2, 2),
                                "?batch_plus", None, "heads", None)
        )(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(z).reshape(4, 4), np.asarray(x))


def test_constrain_rank_mismatch_raises():
    mesh = _mesh2d()
    with use_mesh(mesh):
        with pytest.raises(ValueError):
            constrain(jnp.ones((2, 2)), "batch")


# ------------------------------------------------------------- spec trees
@pytest.mark.parametrize("arch", ["gemma-2b", "qwen2-moe-a2.7b", "zamba2-1.2b"])
def test_param_specs_structure(arch):
    cfg = get_config(arch)
    params_abs = abstract_params(cfg)
    mesh = _FakeMesh(data=4, model=8)
    specs = param_specs(params_abs, mesh, n_experts=cfg.n_experts)
    flat_p = jax.tree_util.tree_flatten_with_path(params_abs)[0]
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    assert len(flat_p) == len(flat_s)
    for (path, leaf), spec in zip(flat_p, flat_s):
        assert isinstance(spec, P)
        assert len(spec) <= len(leaf.shape), (path, spec)
        for ax, entry in enumerate(spec):
            if entry is not None:
                assert entry == "model"
                assert leaf.shape[ax] % mesh.shape["model"] == 0, (path, spec)
    # leading stack (scan) dims never shard
    for (path, leaf), spec in zip(flat_p, flat_s):
        keys = [str(getattr(k, "key", k)) for k in path]
        if keys[0] in ("layers", "groups", "enc_layers", "dec_layers", "tail"):
            assert len(spec) == 0 or spec[0] is None, (path, spec)


def test_param_specs_shard_what_divides():
    """On a mesh whose model axis divides heads/ff/vocab, the big matrices
    actually claim it (not vacuous all-replicated trees)."""
    cfg = get_config("gemma-2b")  # 8 heads, MQA kv=1, ff 16384, vocab 256128
    params_abs = abstract_params(cfg)
    mesh = _FakeMesh(data=4, model=8)
    specs = param_specs(params_abs, mesh, n_experts=cfg.n_experts)
    assert specs["embed"] == P("model", None)
    layer = specs["layers"]
    assert layer["attn"]["wq"] == P(None, None, "model", None)
    assert layer["attn"]["wk"] == P(None, None, None, None)  # kv=1: replicate
    assert layer["attn"]["wo"] == P(None, "model", None, None)
    assert layer["mlp"]["wi"] == P(None, None, None, "model")
    assert layer["mlp"]["wo"] == P(None, "model", None)
    assert layer["ln1"] == P(None, None)  # stacked norm scales: replicated


def test_param_specs_moe_expert_rules():
    cfg = get_config("qwen2-moe-a2.7b")  # 60 experts: indivisible by 8
    params_abs = abstract_params(cfg)
    specs = param_specs(
        params_abs, _FakeMesh(data=4, model=8), n_experts=cfg.n_experts
    )
    moe = specs["layers"]["moe"]
    # 60 experts don't divide model=8 -> the expert-ff dim shards instead,
    # and the leading (layers, experts) stack dims stay unsharded
    assert moe["wi"] == P(None, None, None, None, "model")
    assert moe["wo"] == P(None, None, "model", None)
    assert moe["shared_wi"] == P(None, None, None, "model")
    assert moe["shared_wo"] == P(None, "model", None)


def test_opt_state_and_batch_specs():
    cfg = get_config("gemma-2b")
    params_abs = abstract_params(cfg)
    mesh = _FakeMesh(data=4, model=8)
    pspecs = param_specs(params_abs, mesh, n_experts=cfg.n_experts)
    z = opt_state_specs(params_abs, pspecs, mesh, zero1=True)
    # ZeRO-1 adds 'data' to exactly one previously-unsharded divisible axis
    # (the 18-layer stack dim doesn't divide data=4, so d_model takes it)
    assert z["embed"] == P("model", "data")
    assert z["layers"]["mlp"]["wo"] == P(None, "model", "data")
    assert z["layers"]["ln1"] == P(None, "data")
    noz = opt_state_specs(params_abs, pspecs, mesh, zero1=False)
    assert jax.tree_util.tree_all(
        jax.tree_util.tree_map(
            lambda a, b: a == b, noz, pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )
    )
    b = batch_specs(
        {"tokens": jax.ShapeDtypeStruct((8, 33), jnp.int32)}, mesh
    )
    assert b["tokens"] == P("data", None)
    # indivisible batch: replicate rather than produce an invalid spec
    b1 = batch_specs({"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}, mesh)
    assert b1["tokens"] == P(None, None)
    assert batch_specs(jax.ShapeDtypeStruct((), jnp.int32), mesh) == P()


def test_cache_specs_shapes():
    mesh = _FakeMesh(data=2, model=2)
    cache_abs = {
        "k": jax.ShapeDtypeStruct((4, 2, 64, 2, 32), jnp.float32),
        "v": jax.ShapeDtypeStruct((4, 2, 64, 2, 32), jnp.float32),
        "len": jax.ShapeDtypeStruct((), jnp.int32),
        "ssm": {"S": jax.ShapeDtypeStruct((4, 2, 8, 16, 16), jnp.float32)},
    }
    specs = cache_specs(cache_abs, mesh)
    assert specs["k"] == P(None, "data", None, "model", None)
    assert specs["len"] == P()
    assert specs["ssm"]["S"] == P(None, "data", None, None, None)
    # real-mesh path: NamedShardings materialize for every P leaf
    real = _mesh2d()
    sh = named_shardings(cache_specs(cache_abs, real), real)
    assert all(
        isinstance(s, NamedSharding) for s in jax.tree_util.tree_leaves(
            sh, is_leaf=lambda x: isinstance(x, NamedSharding))
    )


# -------------------------------------------------------------- grad codec
def test_codec_roundtrip_and_ring_homomorphism():
    codec = GradCodec.make(world=32)
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((16, 9)).astype(np.float32))
    packed = codec.encode(g)
    assert packed.shape == g.shape + (codec.base.n + 1,)
    dec = codec.decode(codec.fold(packed))
    np.testing.assert_allclose(
        np.asarray(dec), np.asarray(g), atol=2.0 ** -codec.frac_bits
    )
    # channel-sum of encodings == encoding of the quantized sum
    W = 32
    gs = rng.standard_normal((W, 64)).astype(np.float32)
    summed = jnp.asarray(
        np.sum([np.asarray(codec.encode(jnp.asarray(x))) for x in gs], axis=0)
    )
    dec = codec.decode(codec.fold(summed))
    q = np.clip(np.round(gs.astype(np.float64) * (1 << codec.frac_bits)),
                -codec.qmax, codec.qmax)
    want = q.sum(0) / (1 << codec.frac_bits)
    np.testing.assert_allclose(np.asarray(dec), want, atol=1e-7)
    folded = codec.fold(summed)
    assert bool(np.all(codec.verify_packed(folded)))
    # Alg.-1 sign query on the SUM: normalize re-anchors the m_a channel
    np.testing.assert_array_equal(
        np.asarray(codec.is_negative(codec.normalize(folded))), q.sum(0) < 0
    )
    # transit corruption of the redundant channel is detected
    bad = np.asarray(folded).copy()
    bad[0, -1] = (bad[0, -1] + 1) % codec.base.ma
    assert not bool(codec.verify_packed(jnp.asarray(bad))[0])


def test_codec_sign_and_magnitude_queries():
    codec = GradCodec.make(world=8)
    vals = np.asarray([-77.25, -1e-4, 0.0, 0.5, 123.0], np.float32)
    folded = codec.fold(codec.encode(jnp.asarray(vals)))
    q = np.clip(np.round(vals.astype(np.float64) * (1 << codec.frac_bits)),
                -codec.qmax, codec.qmax).astype(np.int64)
    np.testing.assert_array_equal(
        np.asarray(codec.is_negative(folded)), q < 0
    )
    for thr in (1, 33, 1 << 20, codec.qmax):
        np.testing.assert_array_equal(
            np.asarray(codec.abs_ge(folded, thr)), np.abs(q) >= thr
        )


def test_rns_psum_matches_float_psum():

    codec = GradCodec.make(world=4)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    g = jnp.asarray(
        np.random.default_rng(7).standard_normal(48), jnp.float32
    )
    rns = jax.shard_map(lambda x: rns_psum(codec, x, "data"), mesh=mesh,
                        in_specs=P(), out_specs=P(), check_vma=False)
    fp = jax.shard_map(
        lambda x: jax.lax.psum(x, "data") / jax.lax.psum(
            jnp.ones((), jnp.float32), "data"),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    )
    np.testing.assert_allclose(
        np.asarray(rns(g)), np.asarray(fp(g)), atol=2.0 ** -codec.frac_bits
    )


def test_codec_world_sizing():
    with pytest.raises(ValueError):
        GradCodec.make(world=0)
    small = GradCodec.make(world=2)
    big = GradCodec.make(world=1 << 20)
    assert small.qmax > big.qmax > 0
    assert 2 * small.world * small.qmax < small.base.M


# ------------------------------------------------------------ fingerprints
def test_fingerprint_flip_and_tree_api():
    rng = np.random.default_rng(1)
    a = rng.standard_normal((32, 8)).astype(np.float32)
    fps = tree_fingerprints({"w": a, "nested": {"b": a[:4]}})
    assert set(fps) == {"w", "nested/b"}
    b = a.copy()
    b[3, 3] += 1e-7
    assert tensor_fingerprint(b) != fps["w"]
    assert verify_fingerprints({"w": b, "nested": {"b": a[:4]}}, fps) == ["w"]
    # dtype matters, not just bytes-compatible content
    assert tensor_fingerprint(np.zeros(4, np.int32)) != tensor_fingerprint(
        np.zeros(4, np.float32)
    )


def test_checkpoint_fingerprint_save_verify_restore(tmp_path):
    from repro.train import checkpoint as ckpt

    tree = {
        "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "nested": {"b": jnp.ones((5,), jnp.int32)},
    }
    d = str(tmp_path / "ck")
    ckpt.save(d, 2, tree)
    ckpt.save(d, 6, jax.tree_util.tree_map(lambda x: x + 1, tree))
    assert os.path.basename(find_restorable(d)) == "step_6"
    abs_tree = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )
    got, step, _ = ckpt.restore(d, abs_tree)
    assert step == 6
    np.testing.assert_array_equal(
        np.asarray(got["w"]), np.asarray(tree["w"]) + 1
    )
    # corrupt the newest step -> discovery falls back to the older valid one
    path = os.path.join(d, "step_6", "0.npy")
    arr = np.load(path)
    arr.ravel()[0] += 1
    np.save(path, arr)
    assert os.path.basename(find_restorable(d)) == "step_2"
    with pytest.raises(IOError):
        ckpt.restore(d, abs_tree, step=6)
    got, step, _ = ckpt.restore(d, abs_tree)
    assert step == 2
    # torn save (dir without manifest) is skipped silently
    os.makedirs(os.path.join(d, "step_9"))
    assert os.path.basename(find_restorable(d)) == "step_2"
    assert find_restorable(str(tmp_path / "missing")) is None


def test_checkpoint_fingerprints_align_with_adversarial_key_order(tmp_path):
    """Joined names ('a/b') can sort differently than the nested flatten
    order ('-' < '/'); manifest fingerprints must still align with names."""
    from repro.train import checkpoint as ckpt

    tree = {
        "a": {"b": jnp.arange(4, dtype=jnp.float32)},
        "a-x": jnp.ones((3,), jnp.int32),
    }
    d = str(tmp_path / "ck")
    ckpt.save(d, 1, tree)
    assert os.path.basename(find_restorable(d)) == "step_1"
    abs_tree = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )
    got, step, _ = ckpt.restore(d, abs_tree)
    assert step == 1
    np.testing.assert_array_equal(
        np.asarray(got["a"]["b"]), np.asarray(tree["a"]["b"])
    )
