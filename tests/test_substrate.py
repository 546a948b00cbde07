"""Substrate tests: gradient codec, checkpointing + fingerprints + elastic
restore, optimizer, data pipeline, sharding rules.
"""
import os

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed — pip install -r requirements-dev.txt",
)
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax
import jax.numpy as jnp

from repro.dist.fault import tensor_fingerprint, verify_fingerprints
from repro.dist.grad_codec import GradCodec
from repro.dist.sharding import auto_mesh
from repro.train import checkpoint as ckpt
from repro.train.data import Prefetcher, SyntheticLM
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update


# ------------------------------------------------------------- grad codec
def test_codec_roundtrip_exact():
    codec = GradCodec.make(world=512)
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((64, 33)).astype(np.float32))
    packed = codec.encode(g)
    dec = codec.decode(codec.fold(packed))
    # quantization error only (1/2^frac_bits), no ring error
    np.testing.assert_allclose(np.asarray(dec), np.asarray(g),
                               atol=2.0 ** -codec.frac_bits)


def test_codec_simulated_allreduce_exact():
    """Sum of W replicas' encodings == encoding-sum (ring homomorphism),
    and decode gives the exact integer mean."""
    codec = GradCodec.make(world=64)
    rng = np.random.default_rng(1)
    W = 64
    gs = rng.standard_normal((W, 128)).astype(np.float32)
    packs = [np.asarray(codec.encode(jnp.asarray(g))) for g in gs]
    summed = jnp.asarray(np.sum(packs, axis=0))  # what psum produces
    dec = codec.decode(codec.fold(summed)) / W
    q = np.clip(np.round(gs * (1 << codec.frac_bits)), -codec.qmax, codec.qmax)
    want = q.sum(0) / (1 << codec.frac_bits) / W
    np.testing.assert_allclose(np.asarray(dec), want, atol=1e-7)


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_codec_sign_and_clip_via_paper_compare(data):
    codec = GradCodec.make(world=8)
    v = data.draw(st.floats(-100.0, 100.0, allow_nan=False))
    packed = codec.encode(jnp.asarray([np.float32(v)]))
    folded = codec.fold(packed)
    q = int(np.clip(round(v * (1 << codec.frac_bits)), -codec.qmax, codec.qmax))
    assert bool(codec.is_negative(folded)[0]) == (q < 0)
    thr = data.draw(st.integers(1, codec.qmax))
    assert bool(codec.abs_ge(folded, thr)[0]) == (abs(q) >= thr)


def test_rns_psum_under_shard_map():
    """End-to-end: rns_psum inside shard_map over a CPU 'data' axis of 1."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.dist.grad_codec import rns_psum

    codec = GradCodec.make(world=4)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    g = jnp.asarray(np.random.default_rng(3).standard_normal(32), jnp.float32)
    f = jax.shard_map(
        lambda x: rns_psum(codec, x, "data"), mesh=mesh,
        in_specs=P(), out_specs=P(), check_vma=False,
    )
    out = f(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g),
                               atol=2.0 ** -codec.frac_bits)


# ------------------------------------------------------------ fingerprints
def test_fingerprint_detects_bitflip():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((128, 64)).astype(np.float32)
    fp = tensor_fingerprint(a)
    b = a.copy()
    b[17, 3] = np.float32(np.frombuffer(
        np.uint32(np.frombuffer(b[17, 3].tobytes(), np.uint32)[0] ^ 1).tobytes(),
        np.float32)[0])
    assert tensor_fingerprint(b) != fp
    assert verify_fingerprints({"a": b}, {"a": fp}) == ["a"]
    assert verify_fingerprints({"a": a}, {"a": fp}) == []


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_corruption(tmp_path):
    tree = {
        "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "nested": {"b": jnp.ones((5,), jnp.int32)},
    }
    d = str(tmp_path / "ck")
    ckpt.save(d, 3, tree, extra={"note": "hi"})
    abs_tree = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )
    got, step, extra = ckpt.restore(d, abs_tree)
    assert step == 3 and extra["note"] == "hi"
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))

    # corrupt a tensor -> restore must reject, find_restorable must skip
    path = os.path.join(d, "step_3", "0.npy")
    arr = np.load(path)
    arr.ravel()[0] += 1
    np.save(path, arr)
    with pytest.raises(IOError):
        ckpt.restore(d, abs_tree, step=3)
    assert ckpt.latest_step(d) is None


def test_checkpoint_resume_picks_newest_valid(tmp_path):
    tree = {"w": jnp.zeros((4,), jnp.float32)}
    d = str(tmp_path / "ck")
    ckpt.save(d, 1, tree)
    ckpt.save(d, 5, jax.tree_util.tree_map(lambda x: x + 5, tree))
    # torn save: step_9 dir without manifest (simulates crash mid-save)
    os.makedirs(os.path.join(d, "step_9"))
    assert ckpt.latest_step(d) == 5


def test_checkpoint_elastic_reshard(tmp_path):
    """Save unsharded, restore onto an explicit 1-device NamedSharding —
    the elastic path (mesh change) exercised at CPU scale."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    d = str(tmp_path / "ck")
    ckpt.save(d, 0, tree)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    sh = {"w": NamedSharding(mesh, P("data", "model"))}
    abs_tree = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)}
    got, _, _ = ckpt.restore(d, abs_tree, shardings=sh)
    assert got["w"].sharding == sh["w"]


def test_checkpoint_async(tmp_path):
    tree = {"w": jnp.ones((8, 8), jnp.float32)}
    d = str(tmp_path / "ck")
    t = ckpt.save_async(d, 7, tree)
    t.join()
    assert ckpt.latest_step(d) == 7


# --------------------------------------------------------------- optimizer
def test_adamw_descends():
    cfg = AdamWConfig(lr=0.1, warmup=0, decay_steps=100, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0], jnp.float32)}
    opt = adamw_init(params)
    for _ in range(50):
        grads = {"w": 2 * params["w"]}  # d/dw of w^2
        params, opt, gnorm = adamw_update(cfg, params, grads, opt)
    assert float(jnp.abs(params["w"]).max()) < 1.0
    assert int(opt["step"]) == 50


# -------------------------------------------------------------------- data
def test_data_deterministic_and_prefetch():
    from repro.configs import get_config

    cfg = get_config("gemma-2b").smoke()
    loader = SyntheticLM(cfg, seq=16, batch=4, seed=9)
    b1, b2 = loader.batch_at(10), loader.batch_at(10)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (4, 17)

    pf = Prefetcher(loader, start_step=0, depth=2)
    s0, batch0 = pf.next()
    s1, _ = pf.next()
    pf.close()
    assert (s0, s1) == (0, 1)
    np.testing.assert_array_equal(batch0["tokens"], loader.batch_at(0)["tokens"])


# ----------------------------------------------------------------- sharding
def test_param_spec_rules():
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import param_specs
    from repro.configs import get_config
    from repro.models import abstract_params

    # the mesh is incidental here (the assertions below test the rule
    # function directly)
    mesh = auto_mesh((1, 1), ("data", "model"))
    # fake a 16-wide model axis by monkeypatching shape lookups is overkill;
    # instead test the rule function directly.
    from repro.dist.sharding import _rule

    # divisible heads shard; indivisible replicate (never head_dim)
    assert _rule("wq", (2048, 16, 128), 16, parent="attn") == [None, "model", None]
    assert _rule("wq", (2048, 8, 256), 16, parent="attn") == [None, None, None]
    assert _rule("embed", (256000, 2048), 16) == ["model", None]
    assert _rule("wi", (2048, 2, 16384), 16, parent="mlp") == [None, None, "model"]
    # stacked leaves: stack dims (leading) must NEVER shard
    assert _rule("wo", (48, 16384, 6144), 16, parent="mlp") == [
        None, "model", None]
    assert _rule("wo", (18, 16384, 2048), 16, parent="mlp") == [
        None, "model", None]
    assert _rule("wo", (28, 16, 256, 3072), 16, parent="attn") == [
        None, "model", None, None]
    # MoE: experts when divisible (moonshot 64), else expert-ff (qwen 60)
    assert _rule("wi", (64, 2048, 2, 1408), 16, n_experts=64) == [
        "model", None, None, None]
    assert _rule("wi", (60, 2048, 2, 1408), 16, n_experts=60) == [
        None, None, None, "model"]
    assert _rule("wo", (60, 1408, 2048), 16, n_experts=60) == [
        None, "model", None]  # 60 experts indivisible -> shard expert-ff
    # unstacked shared-block leaves (zamba2) must not crash or shard stacks
    assert _rule("wo", (8192, 2048), 16, parent="mlp") == ["model", None]
    assert _rule("wo", (32, 64, 2048), 16, parent="attn") == ["model", None, None]
