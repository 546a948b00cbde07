"""The plain reference against the program, at a CPU size, and the
dropless capacity the cells run at.

Tolerance: both sides compute in float32 on the CPU, and the reference
draws the program's own weights again from the seed, so the logits and
the K/V rows can differ only by the order of floating-point sums
(flash-style online softmax and capacity-buffer scatter in the program,
plain softmax and a dense expert sum in the reference).  That is a few
ulps of values near 1 after two layers: 1e-4 (absolute for logits,
relative for a K/V row) holds it with room, while one token's wrong
expert or a position off by one moves logits by 1e-2 or more.
"""
import numpy as np
import pytest

import smoke
import harness
import model_config

import jax
import jax.numpy as jnp

REF = harness.module("reference", "qwen1.5-moe-a2.7b")
TOL = 1e-4


def test_reference_draws_the_programs_weights():
    import repro  # noqa: F401
    from repro.launch.serve import serving_params

    spec = smoke.qwen_smoke()
    cfg = model_config.model_config(spec)
    params = serving_params(cfg, 2**31 + 3)
    s = REF.Shapes.of(spec)
    key = jax.random.key(2**31 + 3)
    assert np.array_equal(REF._embed_table(s, key), params["embed"])
    for i in range(cfg.n_layers):
        w = REF._layer(s, key, i)
        pl = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        pairs = {"wq": pl["attn"]["wq"], "wk": pl["attn"]["wk"],
                 "wv": pl["attn"]["wv"], "wo": pl["attn"]["wo"],
                 "router": pl["moe"]["router"], "wi": pl["moe"]["wi"],
                 "we": pl["moe"]["wo"], "swi": pl["moe"]["shared_wi"],
                 "swo": pl["moe"]["shared_wo"]}
        for k, v in pairs.items():
            assert np.array_equal(w[k], v), (i, k)
        assert not np.any(pl["ln1"]) and not np.any(pl["ln2"])


def _program_logits(spec, seed, prompt, n_new):
    """Prefill through the paged pool, then paged decode, with the
    program's own step functions: the logits of each served position."""
    import repro  # noqa: F401
    from repro.launch.serve import serving_params
    from repro.models import decode_step, extend_step
    from repro.serve.serve_step import paged_pool_abstract

    eng = spec["engine"]
    cfg = model_config.model_config(spec)
    params = serving_params(cfg, seed)
    ps, T = eng["page_size"], eng["cache_len"]
    n_pg = T // ps
    pool = jax.tree_util.tree_map(
        lambda l: jnp.zeros(l.shape, l.dtype),
        paged_pool_abstract(cfg, params, 1 + n_pg + 1, ps))
    pages = jnp.arange(1, n_pg + 1, dtype=jnp.int32)[None, :]
    bucket = 64
    plen = len(prompt)
    toks = jnp.asarray([prompt + [0] * (bucket - plen)], jnp.int32)
    logits, pool = extend_step(
        cfg, params, pool, toks, jnp.int32(0), logit_index=jnp.int32(plen - 1),
        pages=pages, page_size=ps, valid_len=jnp.int32(plen),
        scratch=jnp.int32(n_pg + 1))
    first = np.asarray(logits[0, 0])
    rows = [first]
    out = [int(np.argmax(rows[-1]))]
    pos = plen
    for _ in range(n_new - 1):
        lg, pool = decode_step(cfg, params, pool,
                               jnp.asarray([[out[-1]]], jnp.int32),
                               jnp.asarray([pos], jnp.int32),
                               pages=pages, page_size=ps)
        rows.append(np.asarray(lg[0]))
        out.append(int(np.argmax(rows[-1])))
        pos += 1
    # the K/V rows the prefill and the decode wrote, through the pages
    kv = {}
    for name in ("k", "v"):
        x = np.asarray(pool[name])[:, np.asarray(pages[0])]
        kv[name] = x.reshape(x.shape[0], -1, *x.shape[3:])[:, :pos]
    return np.stack(rows), out, kv


@pytest.mark.parametrize("plen", [5, 37])
def test_prefill_then_paged_decode_matches_the_reference(plen):
    spec = smoke.qwen_smoke()
    seed = 11 + plen
    rng = np.random.default_rng(plen)
    prompt = rng.integers(1, 512, plen).tolist()
    prog, out, kv = _program_logits(spec, seed, prompt, 9)
    ref = REF.forward_logits(spec, seed, [{"prompt": prompt, "out": out}])[0]
    assert ref.shape == prog.shape
    assert np.max(np.abs(ref - prog)) < TOL
    got = REF.readings(spec, seed, [dict(prompt=prompt, out=out, **kv)])
    assert got["kv_prompt_err"] < TOL and got["kv_decode_err"] < TOL
    assert len(got["kv_decode_by_layer"]) == spec["num_hidden_layers"]
    assert got["max_logit_gap"] < TOL and got["altered_gap"] > 1e-3
    assert got["tokens"] == 9 and got["mismatches"] == 0
    assert got["kv_rows"] == plen + 8


def test_a_page_of_wrong_rows_shows_in_the_kv_error():
    """Rows written from the wrong position (a K row rolled by one place)
    in every decode row, or a prompt page left zero, read far above the
    limits."""
    spec = smoke.qwen_smoke()
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, 512, 33).tolist()
    _, out, kv = _program_logits(spec, 4, prompt, 9)
    bad = {"k": np.roll(kv["k"], 1, axis=1), "v": kv["v"]}
    got = REF.readings(spec, 4, [dict(prompt=prompt, out=out, **bad)])
    assert got["kv_decode_err"] > REF.KV_DECODE_LIMIT
    zero = {n: kv[n].copy() for n in kv}
    for n in zero:
        zero[n][:, :32] = 0
    got = REF.readings(spec, 4, [dict(prompt=prompt, out=out, **zero)])
    assert got["kv_prompt_err"] > REF.KV_PROMPT_LIMIT
    assert got["kv_decode_err"] < TOL


def test_a_wrong_token_shows_as_a_gap():
    spec = smoke.qwen_smoke()
    prompt = list(range(3, 20))
    _, out, _ = _program_logits(spec, 5, prompt, 6)
    bad = out[:3] + [(out[3] + 1) % 512] + out[4:]
    got = REF.readings(spec, 5, [{"prompt": prompt, "out": bad}])
    assert got["max_logit_gap"] > 1e-3
    ref = REF.forward_logits(spec, 5, [{"prompt": prompt, "out": bad}])[0]
    g = ref.max(-1) - ref[np.arange(6), bad]
    assert g[3] > 1e-3 and np.max(g[:3]) < TOL


def test_capacity_is_dropless_at_every_bucket_the_cells_warm():
    spec = harness.data_file("configs", "qwen1.5-moe-a2.7b")
    cfg = model_config.model_config(spec)
    sizes = (1,) + model_config.buckets(spec["engine"])
    assert model_config.buckets(spec["engine"]) == (
        16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
    for s in sizes:
        assert model_config.expert_capacity(cfg, s) == s, s
