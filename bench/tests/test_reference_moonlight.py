"""Moonlight-16B-A3B's plain reference against the program at a CPU size,
its operation counts, the extend's roofline reader, the dropless capacity
its cell runs at, and a whole run of its cell.

Tolerance: both sides compute in float32 on the CPU, and the reference
draws the program's own weights again from the seed, so the logits and
the latent rows can differ only by the order of floating-point sums
(flash-style online softmax, the absorbed decode's W_UK folded into the
query and capacity-buffer scatter in the program; plain softmax, the
expanded form and a dense expert sum in the reference).  That is a few
ulps of values near 1 after three layers: 1e-4 (absolute for logits,
relative for a latent row) holds it with room, while one token's wrong
expert, a row of the wrong position or a wrong token moves them by 1e-2
or more.
"""
import copy

import numpy as np
import pytest

import smoke
import harness
import model_config

import jax
import jax.numpy as jnp

NAME = "moonlight-16b-a3b"
REF = harness.module("reference", NAME)
WORK = harness.module("work", NAME)
TOL = 1e-4


def moon_smoke(**engine) -> dict:
    """Moonlight's file at a CPU size: dense layer 0 and two expert layers,
    d 64, MLA ranks 32/16/8/16, 8 experts top-2 plus 2 shared, vocab 512,
    float32, dropless."""
    s = copy.deepcopy(harness.data_file("configs", NAME))
    s.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, num_hidden_layers=3, intermediate_size=128,
             moe_intermediate_size=32, n_routed_experts=8,
             num_experts_per_tok=2, vocab_size=512)
    s["engine"].update(param_dtype="float32", dtype="float32",
                       capacity_factor=4.0, n_slots=4, cache_len=256,
                       page_size=16, prefill_chunk=16, n_pages=0,
                       bucket_lo=16)
    s["engine"].update(engine)
    return s


def moon_control() -> dict:
    """A CPU size at which the float8 control's rounding adds up as it
    does at the cell's: the cell's 8 layers and top-6 routing, d 128."""
    s = moon_smoke()
    s.update(hidden_size=128, num_hidden_layers=8, n_routed_experts=16,
             num_experts_per_tok=6, kv_lora_rank=64, qk_nope_head_dim=32,
             qk_rope_head_dim=16, v_head_dim=32, intermediate_size=512,
             moe_intermediate_size=64)
    return s


def long_smoke() -> dict:
    """The cell's mix at a CPU size."""
    mix = harness.data_file("traffic", "long-over")
    mix.update(rate_per_s=8.0, block=4, preroll_s=0.5,
               prompt={"median": 40, "sigma": 0.5, "min": 16, "max": 120},
               output={"median": 6, "sigma": 0.5, "min": 3, "max": 12})
    return mix


def test_reference_draws_the_programs_weights():
    import repro  # noqa: F401
    from repro.launch.serve import serving_params

    spec = moon_smoke()
    cfg = model_config.model_config(spec)
    assert (cfg.attn, cfg.first_dense, cfg.router) == ("mla", 1, "sigmoid_bias")
    params = serving_params(cfg, 2**31 + 3)
    s = REF.Shapes.of(spec)
    key = jax.random.key(2**31 + 3)
    assert np.array_equal(REF._table(s, key, False), params["embed"])
    assert np.array_equal(REF._table(s, key, True), params["head"])
    for i in range(cfg.n_layers):
        w = REF._layer(s, key, i)
        stack, j = (("dense_layers", i) if i < cfg.first_dense
                    else ("layers", i - cfg.first_dense))
        pl = jax.tree_util.tree_map(lambda a: a[j], params[stack])
        a = pl["attn"]
        pairs = {"wq": a["wq"], "wkv_a": a["wkv_a"], "wkv_b": a["wkv_b"],
                 "wo": a["wo"]}
        if i < cfg.first_dense:
            pairs.update(mwi=pl["mlp"]["wi"], mwo=pl["mlp"]["wo"])
        else:
            m = pl["moe"]
            pairs.update(router=m["router"], bias=m["router_bias"],
                         wi=m["wi"], we=m["wo"], swi=m["shared_wi"],
                         swo=m["shared_wo"])
        assert set(pairs) == set(w)
        for k, v in pairs.items():
            assert np.array_equal(w[k], v), (i, k)
        assert not np.any(pl["ln1"]) and not np.any(a["kv_norm"])


def _program(spec, seed, prompt, n_new, bucket=64):
    """Bucketed prefill through the paged pool, then paged decode, with the
    program's own step functions: the logits of each served position,
    the tokens, and the latent rows written, read through the pages."""
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
    plen = len(prompt)
    toks = jnp.asarray([prompt + [0] * (bucket - plen)], jnp.int32)
    logits, pool = extend_step(
        cfg, params, pool, toks, jnp.int32(0), logit_index=jnp.int32(plen - 1),
        pages=pages, page_size=ps, valid_len=jnp.int32(plen),
        scratch=jnp.int32(n_pg + 1))
    rows = [np.asarray(logits[0, 0])]
    out = [int(np.argmax(rows[-1]))]
    for pos in range(plen, plen + n_new - 1):
        lg, pool = decode_step(cfg, params, pool,
                               jnp.asarray([[out[-1]]], jnp.int32),
                               jnp.asarray([pos], jnp.int32),
                               pages=pages, page_size=ps)
        rows.append(np.asarray(lg[0]))
        out.append(int(np.argmax(rows[-1])))
    assert set(pool) == {"c_kv", "k_pe", "len"}
    lat = {}
    for name in ("c_kv", "k_pe"):
        x = np.asarray(pool[name])[:, np.asarray(pages[0])]
        lat[name] = x.reshape(x.shape[0], -1, x.shape[-1])[:, :plen + n_new - 1]
    return np.stack(rows), out, lat


@pytest.mark.parametrize("plen", [5, 37])
def test_prefill_then_paged_decode_matches_the_reference(plen):
    spec = moon_smoke()
    seed = 2**31 + 11 + plen
    prompt = np.random.default_rng(plen).integers(1, 512, plen).tolist()
    prog, out, lat = _program(spec, seed, prompt, 9)
    ref = REF.forward_logits(spec, seed, [{"prompt": prompt, "out": out}])[0]
    assert ref.shape == prog.shape
    assert np.max(np.abs(ref - prog)) < TOL
    got = REF.readings(spec, seed, [dict(prompt=prompt, out=out, **lat)])
    assert got["kv_prompt_err"] < TOL and got["kv_decode_err"] < TOL
    assert len(got["kv_decode_by_layer"]) == spec["num_hidden_layers"]
    assert got["max_logit_gap"] < TOL and got["altered_gap"] > 1e-3
    assert got["tokens"] == 9 and got["mismatches"] == 0
    assert got["kv_rows"] == plen + 8


def test_a_wrong_page_and_a_wrong_token_show():
    """Latent rows one position off in every decode row, or c_kv left zero
    on two of the prompt's three pages, read far above the limits; a
    served token altered reads as a gap."""
    spec = moon_smoke()
    prompt = np.random.default_rng(1).integers(1, 512, 33).tolist()
    _, out, lat = _program(spec, 4, prompt, 9)
    shifted = dict(lat, k_pe=np.roll(lat["k_pe"], 1, axis=1))
    got = REF.readings(spec, 4, [dict(prompt=prompt, out=out, **shifted)])
    assert got["kv_decode_err"] > REF.KV_DECODE_LIMIT
    zero = dict(lat, c_kv=lat["c_kv"].copy())
    zero["c_kv"][:, :32] = 0
    got = REF.readings(spec, 4, [dict(prompt=prompt, out=out, **zero)])
    assert got["kv_prompt_err"] > REF.KV_PROMPT_LIMIT
    assert got["kv_decode_err"] < TOL
    bad = out[:3] + [(out[3] + 1) % 512] + out[4:]
    got = REF.readings(spec, 4, [{"prompt": prompt, "out": bad}])
    assert got["max_logit_gap"] > 1e-3


def test_capacity_is_dropless_at_every_bucket_the_cell_warms():
    spec = harness.data_file("configs", NAME)
    cfg = model_config.model_config(spec)
    assert model_config.buckets(spec["engine"]) == (
        16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
    for s in (1,) + model_config.buckets(spec["engine"]):
        assert model_config.expert_capacity(cfg, s) >= s, s


def test_counts_by_hand_at_a_small_size():
    s = moon_smoke()
    # d 64, H 4, rank 32, nope 16, rope 8, v 16, L 3 (1 dense), E 8 top-2,
    # expert ff 32, shared 2 x 32, dense ff 128, vocab 512
    proj = 64 * 4 * 24 + 64 * 40 + 4 * 16 * 64            # 12800
    absorb = 4 * 32 * (16 + 16)                             # 4096
    experts = 2 * 3 * 64 * 32 + 3 * 64 * 64 + 64 * 8        # 25088
    ffn = 3 * 64 * 128 + 2 * experts                        # 74752
    lin = 2 * (3 * (proj + absorb) + ffn + 64 * 512)
    assert WORK.linear_flops_per_token(s) == lin
    # absorbed: per position and layer 2 * H * (2 * rank + rope)
    assert WORK.attn_flops(s, 10) == 2 * 3 * 4 * (64 + 8) * 10
    flops, nbytes = WORK.decode_step(s, [10])
    attn_w = proj + 32 * 4 * 32
    weights = 4 * (attn_w + 3 * 64 * 128) + 2 * 4 * (
        attn_w + 3 * 64 * 64 + 64 * 8 + 8 + 2 * 3 * 64 * 32) + 512 * 64 * 4
    assert flops == WORK.token_flops(s, 10)
    assert nbytes == weights + 3 * 40 * 4 * 11
    # prefill of 3 tokens after 5: rows 0..7 expanded once, 3+4... pairs
    pf, pb = WORK.prefill_step(s, 5, 3)
    pairs = 6 + 7 + 8
    want = 2 * (3 * proj + ffn) * 3 + 2 * 64 * 512 \
        + 2 * 3 * 32 * 4 * 32 * 8 + 2 * 3 * 4 * (24 + 16) * pairs
    assert pf == want
    assert WORK.prefill_flops(s, 5, 3) == want + 2 * 64 * 512 * 2
    assert pb == WORK.weight_bytes(s, 6) + 3 * 40 * 4 * (5 + 6)


def test_full_size_counts():
    s = harness.data_file("configs", NAME)
    # the 9.7 GB held less the 0.67 GB embedding, whose rows a step reads
    # a few of: every expert is read by a 64-row step
    assert 8.9e9 < WORK.weight_bytes(s, 64) < 9.1e9
    _, nbytes = WORK.decode_step(s, [4096] * 64)
    assert nbytes - WORK.weight_bytes(s, 64) == 8 * 1152 * 4097 * 64
    assert WORK.attn_flops(s, 1) == 8 * 2 * 16 * (576 + 512)


def test_extend_roofline_reads_nothing_without_prefill_step():
    import types

    reader = harness.metric_reader("extend_roofline.long")
    trace = types.SimpleNamespace(module_s=lambda pred: (0.5, 2))
    win = types.SimpleNamespace(admits=[{"t": 1.0, "start": 0, "plen": 100}])
    run = types.SimpleNamespace(trace=trace, trace_span=(0.0, 2.0),
                                window=win, spec=harness.data_file(
                                    "configs", NAME),
                                peaks=smoke.PEAKS, work=None)
    assert reader.read(run) is None
    run.work = harness.module("work", "qwen1.5-moe-a2.7b")
    assert reader.read(run) is None
    run.work = WORK
    flops, nbytes = WORK.prefill_step(run.spec, 0, 100)
    want = 100 * max(flops / 197e12, nbytes / 819e9) / 0.5
    assert reader.read(run) == pytest.approx(want)


def test_a_whole_run_of_the_cell_on_the_cpu():
    """The cell's own entry, at a CPU size: correct, and every one of its
    eleven per-layer readers runs on the trace; the CPU trace has no chip,
    so only the program's own counters read a number."""
    mix = long_smoke()
    names = [m["name"] for m in harness.cell_metrics(
        harness.load_spec(), "moon.long-over", trace=True)]
    assert len(names) == 11 and all(n.endswith(".long") for n in names)
    res, err = smoke.run_smoke("moon.long-over", moon_smoke(), mix,
                               seconds=3.0, trace=1)
    assert res["correct"], err
    assert set(res["metrics"]) <= set(names)
    assert res["metrics"]["slot_occupancy.long"]["value"] > 0
    res, _ = smoke.run_smoke("moon.long-over", moon_smoke(), mix,
                             seconds=1.5, trace=0)
    assert res["correct"] and set(res["metrics"]) == {"tokens_per_s",
                                                      "setup_s"}


def test_fp8_control_moves_the_latent_rows_beyond_the_limits():
    """The control: the reference computed with float8 (e4m3) operands,
    the step below the configuration's bfloat16.  On the chip it is read
    by ``bench/control.py`` at the cell's own size (PERF.md gives the
    readings); here at ``moon_control``'s size."""
    spec = moon_control()
    rng = np.random.default_rng(0)
    seqs = [{"prompt": rng.integers(1, 512, 40).tolist(),
             "out": rng.integers(1, 512, 30).tolist()}]
    f32 = REF.forward_logits(spec, 3, seqs)[0]
    f8 = REF.forward_logits(spec, 3, seqs, lp=True)[0]
    assert np.abs(f8 - f32).max() > 1e-2 * np.abs(f32).max()
    got = REF.readings(spec, 3, seqs, control=True)
    assert got["tokens"] == 30 and got["kv_rows"] == 69
    assert got["kv_prompt_err"] > REF.KV_PROMPT_LIMIT
    assert got["kv_decode_err"] > REF.KV_DECODE_LIMIT


def test_a_run_with_the_control_in_the_programs_place_is_not_correct(
        monkeypatch):
    good = REF.readings
    monkeypatch.setattr(REF, "readings", lambda spec, seed, seqs: good(
        spec, seed, seqs, control=True))
    res, err = smoke.run_smoke("moon.long-over", moon_control(), long_smoke(),
                               seconds=2.0)
    assert not res["correct"]
    assert not res["checks"]["kv_prompt_err"]["ok"]
    assert not res["checks"]["kv_decode_err"]["ok"]
    assert "check kv_decode_err" in err and "FAILED" in err
