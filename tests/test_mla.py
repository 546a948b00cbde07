"""Moonlight-16B-A3B's mechanisms at a smoke size, float32, seeded weights:
latent attention (MLA) in its absorbed and expanded forms over one latent
cache, the paged extend + decode against the full forward, the sigmoid
bias-corrected router, the leading dense layer, the untied head and the
page fingerprints of the latent leaves.

Tolerances: every path here computes in float32 on the CPU from the same
weights, so two paths differ only by the order of floating-point sums
(the absorbed form contracts W_UK into q before the latent rows, the
expanded form after; flash-style online softmax against a plain one).
That is a few ulps of values of order 1: 1e-4 (relative, or absolute on
logits of order 1) holds it with room, while a wrong position, a row of
the wrong page or a wrong expert moves them by 1e-2 or more.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.configs import get_config
from repro.models import decode_step, extend_step, init_params, prefill, train_logits
from repro.models import mla
from repro.models.mla import mla_decode, mla_decode_paged
from repro.models.moe import moe_forward, route
from repro.serve.batcher import ContinuousBatcher
from repro.serve.scheduler import Request
from repro.serve.serve_step import paged_pool_abstract

TOL = 1e-4


@pytest.fixture(scope="module")
def cfg():
    """The smoke size of the corrected preset: dense layer 0 and one
    expert layer, MLA ranks 32/16/8/16, 8 experts top-2, dropless."""
    c = get_config("moonshot-v1-16b-a3b").smoke()
    assert (c.attn, c.first_dense, c.router, c.tie_embeddings) == (
        "mla", 1, "sigmoid_bias", False)
    return dataclasses.replace(
        c, capacity_factor=c.n_experts / c.top_k).validate()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.key(3))


def _close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


def test_params_hold_a_dense_layer_an_expert_stack_and_an_untied_head(cfg, params):
    assert params["dense_layers"]["mlp"]["wi"].shape == (1, 128, 2, cfg.d_ff)
    assert "moe" not in params["dense_layers"]
    assert params["layers"]["moe"]["router_bias"].shape == (1, cfg.n_experts)
    attn = params["layers"]["attn"]
    assert attn["wkv_a"].shape == (1, 128, cfg.kv_lora_rank + cfg.qk_rope_dim)
    assert attn["wkv_b"].shape == (1, cfg.kv_lora_rank, cfg.n_heads,
                                   cfg.qk_nope_dim + cfg.v_head_dim)
    assert params["head"].shape == params["embed"].shape
    assert not np.array_equal(params["head"], params["embed"])
    tied = init_params(get_config("qwen2-moe-a2.7b").smoke(), jax.random.key(0))
    assert "head" not in tied and "dense_layers" not in tied


@pytest.mark.parametrize("s", [1, 3])
def test_absorbed_decode_equals_the_expanded_form(cfg, params, s):
    """The same layer, latent rows and queries at per-row positions: the
    absorbed form (W_UK folded into q, W_UV after the weighted sum) and the
    expanded form (per-head K and V from the latent rows) agree."""
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["attn"])
    rng = np.random.default_rng(s)
    b, S = 2, 32
    x = jnp.asarray(rng.standard_normal((b, s, cfg.d_model)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((b, S, cfg.kv_lora_rank)), jnp.float32)
    pe = jnp.asarray(rng.standard_normal((b, S, cfg.qk_rope_dim)), jnp.float32)
    qpos = jnp.asarray([[5], [19]]) + jnp.arange(s)[None]
    q_nope, q_pe, _, _ = mla._project(p, cfg, x, qpos)
    outs = [np.asarray(mla._attend(p, cfg, q_nope, q_pe, c, pe, qpos,
                                   absorbed=a)) for a in (True, False)]
    assert _close(outs[0], outs[1])
    assert np.max(np.abs(outs[0])) > 1e-3


def test_paged_decode_reads_the_rows_the_linear_decode_reads(cfg, params):
    """One token per row at per-row positions: through a page table whose
    pages lie in reverse order, the decode writes and reads the same rows
    as on a linear cache (a gather in the wrong order would show)."""
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["attn"])
    rng = np.random.default_rng(7)
    b, S, ps = 2, 32, 8
    n = S // ps
    x = jnp.asarray(rng.standard_normal((b, 1, cfg.d_model)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((b, S, cfg.kv_lora_rank)), jnp.float32)
    pe = jnp.asarray(rng.standard_normal((b, S, cfg.qk_rope_dim)), jnp.float32)
    pos = jnp.asarray([5, 19], jnp.int32)
    want, new = mla_decode(p, cfg, x, {"c_kv": c, "k_pe": pe}, pos)
    pages = jnp.asarray([[1 + r * n + (n - 1 - j) for j in range(n)]
                         for r in range(b)], jnp.int32)
    cp = jnp.zeros((1 + b * n, ps, cfg.kv_lora_rank)).at[pages].set(
        c.reshape(b, n, ps, -1))
    pp = jnp.zeros((1 + b * n, ps, cfg.qk_rope_dim)).at[pages].set(
        pe.reshape(b, n, ps, -1))
    got, cp, pp = mla_decode_paged(p, cfg, x, cp, pp, pages, pos,
                                   page_size=ps)
    assert _close(got, want)
    assert _close(cp[pages].reshape(b, S, -1), new["c_kv"])
    assert _close(pp[pages].reshape(b, S, -1), new["k_pe"])
    assert not np.array_equal(new["c_kv"][0, 5], c[0, 5])  # written


def _paged_serve(cfg, params, prompt, n_new, *, bucket=16, ps=8, cache_len=32):
    """Bucketed paged extend of ``prompt`` (pads through the scratch page)
    then paged one-token decodes of the given continuation: the logits of
    every prompt position and of each decode, and the final pool."""
    n_pg = cache_len // ps
    pool = jax.tree_util.tree_map(
        lambda l: jnp.zeros(l.shape, l.dtype),
        paged_pool_abstract(cfg, params, 2 + n_pg, ps))
    assert set(pool) == {"c_kv", "k_pe", "len"}
    assert pool["c_kv"].shape == (cfg.n_layers, 2 + n_pg, ps, cfg.kv_lora_rank)
    assert pool["k_pe"].shape == (cfg.n_layers, 2 + n_pg, ps, cfg.qk_rope_dim)
    pages = jnp.arange(1, n_pg + 1, dtype=jnp.int32)[None]
    plen = len(prompt)
    toks = jnp.asarray([prompt + [0] * (bucket - plen)], jnp.int32)
    logits, pool = extend_step(cfg, params, pool, toks, jnp.int32(0),
                               pages=pages, page_size=ps,
                               valid_len=jnp.int32(plen),
                               scratch=jnp.int32(n_pg + 1))
    rows = [np.asarray(logits[0, :plen])]
    for i, t in enumerate(n_new):
        lg, pool = decode_step(cfg, params, pool, jnp.asarray([[t]], jnp.int32),
                               jnp.asarray([plen + i], jnp.int32),
                               pages=pages, page_size=ps)
        rows.append(np.asarray(lg))
    return np.concatenate(rows), pool


def test_paged_extend_then_decode_reproduce_the_full_forward(cfg, params):
    rng = np.random.default_rng(0)
    toks = [int(t) for t in rng.integers(1, cfg.vocab, 24)]
    full, _ = train_logits(cfg, params, {"tokens": jnp.asarray([toks])})
    got, pool = _paged_serve(cfg, params, toks[:13], toks[13:])
    assert got.shape == (24, cfg.vocab)
    assert _close(got, np.asarray(full[0]))
    # prefill's latent cache holds the same rows the pool does
    _, cache = prefill(cfg, params, {"tokens": jnp.asarray([toks])}, 32)
    for n in ("c_kv", "k_pe"):
        rows = np.asarray(pool[n])[:, 1:5].reshape(cfg.n_layers, 32, -1)
        assert _close(rows[:, :24], np.asarray(cache[n])[:, 0, :24])


def test_router_selects_by_biased_score_and_weights_by_the_unbiased_one(cfg):
    """A bias large enough to move the choice: a router that ignores the
    bias picks other experts; one that weights with it gets other gates."""
    E, K, d = cfg.n_experts, cfg.top_k, cfg.d_model
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 5, d)), jnp.float32)
    w = jnp.asarray(0.05 * rng.standard_normal((d, E)), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(x[0] @ w), np.float64)
    bias = np.zeros(E)
    bias[np.argmin(scores[0])] = 0.9        # token 0's worst expert now wins
    bias[np.argmax(scores[0])] = -0.9       # and its best one loses
    p = {"router": w, "router_bias": jnp.asarray(bias, jnp.float32)}
    gates, idx, _ = route(p, cfg, x)
    gates, idx = np.asarray(gates[0], np.float64), np.asarray(idx[0])
    want_idx = np.argsort(-(scores + bias), axis=-1)[:, :K]
    assert (np.sort(idx, -1) == np.sort(want_idx, -1)).all()
    assert (np.sort(idx[0]) != np.sort(np.argsort(-scores[0])[:K])).any()
    chosen = np.take_along_axis(scores, idx, -1)
    want = chosen / chosen.sum(-1, keepdims=True) * cfg.routed_scale
    assert np.max(np.abs(gates - want)) < 1e-6
    biased = np.take_along_axis(scores + bias, idx, -1)
    assert np.max(np.abs(gates - biased / biased.sum(-1, keepdims=True)
                         * cfg.routed_scale)) > 1e-2
    assert np.allclose(gates.sum(-1), cfg.routed_scale)

    # the dispatch applies those gates, and adds the shared experts ungated
    mp = jax.tree_util.tree_map(lambda a: a[0], init_params(
        cfg, jax.random.key(5))["layers"]["moe"])
    mp = dict(mp, router=w, router_bias=p["router_bias"])
    y, _ = moe_forward(mp, cfg, x)
    silu = jax.nn.silu
    xs = np.asarray(x[0], np.float64)
    u = np.einsum("td,edgf->tegf", xs, np.asarray(mp["wi"], np.float64))
    ye = np.einsum("tef,efd->ted", np.asarray(silu(u[..., 0, :])) * u[..., 1, :],
                   np.asarray(mp["wo"], np.float64))
    dense = np.zeros((5, E))
    np.put_along_axis(dense, idx, gates, -1)
    us = np.einsum("td,dgf->tgf", xs, np.asarray(mp["shared_wi"], np.float64))
    ref = np.einsum("te,ted->td", dense, ye) + (
        np.asarray(silu(us[:, 0])) * us[:, 1]) @ np.asarray(mp["shared_wo"])
    assert _close(y[0], ref)


def test_the_untied_head_is_what_the_logits_read(cfg, params):
    """With ``head`` zeroed every path's logits are zero, though the
    embedding that the tokens read is untouched."""
    p0 = dict(params, head=jnp.zeros_like(params["head"]))
    toks = jnp.asarray([[3, 5, 7, 9]], jnp.int32)
    lg, _ = train_logits(cfg, p0, {"tokens": toks})
    last, cache = prefill(cfg, p0, {"tokens": toks}, 8)
    dec, _ = decode_step(cfg, p0, cache, toks[:, :1], jnp.int32(4))
    got, _ = _paged_serve(cfg, p0, [3, 5, 7], [9], bucket=4, ps=4, cache_len=8)
    for x in (lg, last, dec, got):
        assert not np.any(np.asarray(x))
    lg1, _ = train_logits(cfg, params, {"tokens": toks})
    assert np.max(np.abs(np.asarray(lg1))) > 1e-3


@pytest.mark.parametrize("leaf", ["c_kv", "k_pe"])
def test_fingerprints_catch_one_corrupt_latent_element(cfg, params, leaf):
    """The engine's pool holds only the latent leaves; one element of one
    prompt page of one leaf bumped fails only its own request's verify."""
    eng = ContinuousBatcher(cfg, params, n_slots=2, cache_len=64,
                            prefill_chunk=8, page_size=8, rns_verify=True,
                            prefill_buckets=(8, 16, 32, 64))
    assert set(eng.cache) == {"c_kv", "k_pe", "len"}
    assert eng._fp_width() == 2 * cfg.n_layers
    rng = np.random.default_rng(9)
    for rid, n in ((0, 33), (1, 29)):
        eng.submit(Request(rid=rid, prompt=[int(t) for t in rng.integers(
            1, cfg.vocab, n)], max_new=3))
    assert len(eng.try_admit()) == 2
    slot = next(s for s in eng.sched.slots if s.req and s.req.rid == 0)
    pages = [pid for lp, pid in eng.sched.slot_pages(slot.index)
             if lp * 8 < 33]
    x = eng.cache[leaf]
    eng.cache = {**eng.cache, leaf: x.at[cfg.n_layers - 1, pages[1], 3, 1].add(
        jnp.asarray(0.5, x.dtype))}
    eng.run_to_completion()
    assert {r for r, ok in eng.verify_log.items() if not ok} == {0}
    assert len(eng.verify_log) == 2
    assert eng.wire.stats["failed"] == 1
