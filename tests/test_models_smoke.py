"""Per-architecture smoke tests: REDUCED same-family configs, one forward /
train-loss / prefill+decode step on CPU; asserts shapes and finiteness.

Full configs are exercised only via the allocation-free dry-run
(launch/dryrun.py); these tests prove the family code paths are sound.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config
from repro.models import decode_step, init_params, prefill, train_logits

B, S = 2, 32


def _batch(cfg, rng):
    batch = {
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, size=(B, S), dtype=np.int32)
        )
    }
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_patches, cfg.d_model), dtype=np.float32)
        )
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.enc_frames, cfg.d_model), dtype=np.float32)
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_forward(arch):
    cfg = get_config(arch).smoke().validate()
    rng = np.random.default_rng(0)
    params = init_params(cfg, jax.random.key(0))
    logits, aux = jax.jit(lambda p, b: train_logits(cfg, p, b))(params, _batch(cfg, rng))
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all()), "NaN/inf in logits"
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode(arch):
    cfg = get_config(arch).smoke().validate()
    rng = np.random.default_rng(1)
    params = init_params(cfg, jax.random.key(1))
    prefix = cfg.n_patches if cfg.family == "vlm" else 0
    s_total = S + prefix  # vlm caches cover the patch prefix too
    cache_len = s_total + 4
    last, cache = jax.jit(
        lambda p, b: prefill(cfg, p, b, cache_len)
    )(params, _batch(cfg, rng))
    assert last.shape == (B, cfg.vocab)
    assert bool(jnp.isfinite(last.astype(jnp.float32)).all())

    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
    logits, cache2 = jax.jit(
        lambda p, c, t: decode_step(cfg, p, c, t, jnp.int32(s_total))
    )(params, cache, tok)
    assert logits.shape == (B, cfg.vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert int(cache2["len"]) == s_total + 1


def test_decode_matches_prefill_on_dense():
    """Consistency: decoding token s with a cache built from tokens[:s] must
    reproduce the training forward's logits at position s (dense arch)."""
    cfg = get_config("gemma-2b").smoke().validate()
    rng = np.random.default_rng(2)
    params = init_params(cfg, jax.random.key(2))
    batch = _batch(cfg, rng)
    full_logits, _ = train_logits(cfg, params, batch)

    prompt = {"tokens": batch["tokens"][:, : S - 1]}
    # pad prompt to chunk boundary is not needed (S-1=31 < q_chunk)
    _, cache = prefill(cfg, params, prompt, cache_len=S + 4)
    logits, _ = decode_step(
        cfg, params, cache, batch["tokens"][:, S - 1 :], jnp.int32(S - 1)
    )
    np.testing.assert_allclose(
        np.asarray(logits, np.float32),
        np.asarray(full_logits[:, -1], np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_ssm_decode_matches_forward():
    """Same consistency check for the SSD recurrence (chunked vs stepwise)."""
    cfg = get_config("mamba2-370m").smoke().validate()
    rng = np.random.default_rng(3)
    params = init_params(cfg, jax.random.key(3))
    batch = _batch(cfg, rng)
    full_logits, _ = train_logits(cfg, params, batch)

    prompt = {"tokens": batch["tokens"][:, : S - 16]}  # chunk multiple (16)
    _, cache = prefill(cfg, params, prompt, cache_len=S)
    logits, cache = decode_step(
        cfg, params, cache, batch["tokens"][:, S - 16 : S - 15], jnp.int32(S - 16)
    )
    # step a few more tokens and compare the last
    for i in range(S - 15, S):
        logits, cache = decode_step(
            cfg, params, cache, batch["tokens"][:, i : i + 1], jnp.int32(i)
        )
    np.testing.assert_allclose(
        np.asarray(logits, np.float32),
        np.asarray(full_logits[:, -1], np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_kv_quant_decode_close_to_bf16():
    """int8 KV cache: decode logits stay close to the unquantized path."""
    import dataclasses

    cfg = get_config("gemma-7b").smoke().validate()
    qcfg = dataclasses.replace(cfg, kv_quant=True)
    rng = np.random.default_rng(5)
    params = init_params(cfg, jax.random.key(5))
    batch = _batch(cfg, rng)

    _, cache = prefill(cfg, params, batch, cache_len=S + 4)
    _, qcache = prefill(qcfg, params, batch, cache_len=S + 4)
    assert qcache["k"].dtype == jnp.int8

    tok = batch["tokens"][:, :1]
    l1, _ = decode_step(cfg, params, cache, tok, jnp.int32(S))
    l2, _ = decode_step(qcfg, params, qcache, tok, jnp.int32(S))
    # int8 quantization error is small relative to logit scale
    denom = float(jnp.std(l1.astype(jnp.float32)))
    err = float(jnp.max(jnp.abs(l1.astype(jnp.float32) - l2.astype(jnp.float32))))
    assert err < 0.15 * max(denom, 1.0), (err, denom)


def test_windowed_ring_cache_matches_forward():
    """gemma3-style grouped window cache: decode with ring buffers must
    reproduce the training forward's last-position logits."""
    cfg = get_config("gemma3-1b").smoke().validate()
    assert cfg.window and cfg.window_cache
    rng = np.random.default_rng(7)
    params = init_params(cfg, jax.random.key(7))
    batch = _batch(cfg, rng)
    full_logits, _ = train_logits(cfg, params, batch)

    prompt = {"tokens": batch["tokens"][:, : S - 1]}
    _, cache = prefill(cfg, params, prompt, cache_len=S + 4)
    assert "lk" in cache and cache["lk"].shape[2] == cfg.window
    logits, cache2 = decode_step(
        cfg, params, cache, batch["tokens"][:, S - 1 :], jnp.int32(S - 1)
    )
    np.testing.assert_allclose(
        np.asarray(logits, np.float32),
        np.asarray(full_logits[:, -1], np.float32),
        rtol=2e-2, atol=2e-2,
    )
    assert int(cache2["len"]) == S


def test_windowed_ring_cache_long_decode():
    """Ring wrap-around: decode several tokens past the window size and
    compare against the mask-only (full cache) implementation."""
    import dataclasses

    cfg = get_config("gemma3-1b").smoke().validate()
    cfg = dataclasses.replace(cfg, window=8)  # tiny window, S=32 >> W
    ref_cfg = dataclasses.replace(cfg, window_cache=False)
    rng = np.random.default_rng(8)
    params = init_params(cfg, jax.random.key(8))
    batch = _batch(cfg, rng)

    prompt = {"tokens": batch["tokens"][:, : S - 4]}
    _, cache = prefill(cfg, params, prompt, cache_len=S + 4)
    _, ref_cache = prefill(ref_cfg, params, prompt, cache_len=S + 4)
    for i in range(S - 4, S):
        tok = batch["tokens"][:, i : i + 1]
        logits, cache = decode_step(cfg, params, cache, tok, jnp.int32(i))
        ref_logits, ref_cache = decode_step(
            ref_cfg, params, ref_cache, tok, jnp.int32(i)
        )
        np.testing.assert_allclose(
            np.asarray(logits, np.float32),
            np.asarray(ref_logits, np.float32),
            rtol=2e-2, atol=2e-2,
        )


def test_ssd_gradients_finite_under_strong_decay():
    """A full chunk of strong decay (the published mamba2 chunk of 128 with
    |dt*A| near its init maximum) drives exp(cum_i - cum_j) past the f32
    range above the diagonal.  Those entries are masked out of the
    forward; their gradients must not turn into NaN (0 * inf)."""
    import dataclasses

    from repro.train.train_step import make_loss_fn

    cfg = dataclasses.replace(get_config("mamba2-370m").smoke(),
                              ssm_chunk=128).validate()
    params = init_params(cfg, jax.random.key(0))
    layers = params["layers"]
    layers["mamba"]["A_log"] = jnp.full_like(layers["mamba"]["A_log"],
                                             np.log(16.0))
    layers["mamba"]["dt_bias"] = jnp.full_like(layers["mamba"]["dt_bias"],
                                               2.0)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, size=(1, 129), dtype=np.int32))
    grads = jax.grad(lambda p: make_loss_fn(cfg)(p, {"tokens": toks})[0])(
        params)
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(leaf)).all()
