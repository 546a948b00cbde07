"""Crypto service tests: Montgomery-over-RnsArray exactness (jnp and
Pallas bitwise-identical), modexp == pow() across multi-limb bases, the
engine's second request family (oracle results, fingerprint verify,
corrupt/repair, no-retrace), the mixed-workload isolation invariant
(bitwise against a crypto-free engine of the same geometry, and against
the plain reference of ``conftest``), and the launcher's crypto trace
family.

Every assertion is differential against Python's big ints — the whole
point of the crypto workload as a TEST program: pow()/divmod() are an
oracle the RNS dataflow cannot fool.
"""
import json
import math
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro  # noqa: F401
from conftest import check_against_reference, snapshot_rows
from repro.configs import get_config
from repro.core import backend, rns_to_int
from repro.core.array import Layout, RnsArray
from repro.core.base import RNSBase, gen_coprime_moduli
from repro.core.montgomery import (
    DualRep,
    RNSMontgomery,
    ladder_step,
    mont_consts,
    mont_mul,
)
from repro.models import init_params
from repro.serve.batcher import ContinuousBatcher
from repro.serve.crypto import CryptoContext, CryptoLane, CryptoRequest
from repro.serve.scheduler import Request

CACHE_LEN = 32
CHUNK = 8


@pytest.fixture(scope="module")
def cfg():
    return get_config("gemma-2b").smoke()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.key(0))


def _bases(n_limbs: int) -> tuple[RNSBase, RNSBase, int]:
    """A dual Montgomery base pair (interleaved draw) + spare modulus for
    RRNS layouts."""
    k = n_limbs
    ms = gen_coprime_moduli(2 * k + 3, 15)
    B = RNSBase(moduli=tuple(ms[0:2 * k:2]), ma=ms[2 * k], bits=15)
    Bp = RNSBase(moduli=tuple(ms[1:2 * k:2]), ma=ms[2 * k + 1], bits=15)
    return B, Bp, ms[2 * k + 2]


def _dual_of(B, Bp, vals, *, mb=None):
    """Host-exact DualRep of a batch of big ints (< 2N in practice)."""
    lo_t = tuple(B.moduli) + (B.ma,) + ((mb,) if mb else ())
    lo = [[v % t for t in lo_t] for v in vals]
    hi = [list(Bp.residues_of(v)) for v in vals]
    return DualRep(
        RnsArray.from_packed(B, jnp.asarray(lo, B.dtype), mb=mb),
        RnsArray.from_packed(Bp, jnp.asarray(hi, Bp.dtype)),
    )


# --------------------------------------------------- kernel == reference
@pytest.mark.parametrize("layout", [Layout.BASE_MA, Layout.RRNS])
def test_mont_mul_pallas_bitwise_matches_jnp(layout):
    """One Montgomery product: the fused Pallas kernel must equal the
    pure-jnp reference BITWISE on every channel (redundant ones too),
    and both must equal the x*y*M^{-1} mod N big-int oracle."""
    B, Bp, spare = _bases(6)
    mb = spare if layout is Layout.RRNS else None
    N = (B.M // 5) | 1
    while math.gcd(N, B.M * Bp.M) != 1:
        N += 2
    c = mont_consts(B, Bp, N, layout=layout, mb=mb)
    rng = random.Random(7)
    xs = [rng.randrange(2 * N) for _ in range(5)]
    ys = [rng.randrange(2 * N) for _ in range(5)]
    x = _dual_of(B, Bp, xs, mb=mb)
    y = _dual_of(B, Bp, ys, mb=mb)
    outs = {}
    for name in ("jnp", "pallas"):
        with backend(name):
            r = mont_mul(x, y, c["neg"], c["n_hi"])
        outs[name] = (np.asarray(r.lo.to_packed()),
                      np.asarray(r.hi.to_packed()))
    np.testing.assert_array_equal(outs["jnp"][0], outs["pallas"][0])
    np.testing.assert_array_equal(outs["jnp"][1], outs["pallas"][1])
    Minv = pow(B.M, -1, N)
    lo_t = tuple(B.moduli) + (B.ma,) + ((mb,) if mb else ())
    for i, (a, b) in enumerate(zip(xs, ys)):
        R = rns_to_int(B, outs["jnp"][0][i][: B.n])
        assert R < 2 * N and R % N == (a * b * Minv) % N
        # redundant channels carry the TRUE residues of R (< 2N < M,
        # no wrap) — that is what makes the wire fingerprints work
        assert [int(v) for v in outs["jnp"][0][i]] == [R % t for t in lo_t]


def test_ladder_step_pallas_bitwise_matches_jnp():
    """The fused ladder-bit kernel (2 products + branchless select) ==
    the jnp composition, bitwise, for both bit values in one batch."""
    B, Bp, _ = _bases(6)
    N = (B.M // 6) | 1
    while math.gcd(N, B.M * Bp.M) != 1:
        N += 2
    c = mont_consts(B, Bp, N)
    rng = random.Random(11)
    r0 = _dual_of(B, Bp, [rng.randrange(2 * N) for _ in range(6)])
    r1 = _dual_of(B, Bp, [rng.randrange(2 * N) for _ in range(6)])
    bit = jnp.asarray([0, 1, 0, 1, 1, 0], jnp.int32)
    outs = {}
    for name in ("jnp", "pallas"):
        with backend(name):
            a, b = ladder_step(r0, r1, bit, c["neg"], c["n_hi"])
        outs[name] = [np.asarray(p) for p in
                      (a.lo.to_packed(), a.hi.to_packed(),
                       b.lo.to_packed(), b.hi.to_packed())]
    for got, want in zip(outs["pallas"], outs["jnp"]):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n_limbs", [6, 12])
@pytest.mark.parametrize("backend_name", ["jnp", "pallas"])
def test_modexp_matches_pow_oracle(n_limbs, backend_name):
    """Fixed-width Montgomery-ladder modexp == pow(a, e, N) on multi-limb
    bases (90 and 180 bits of range), under BOTH backends — the ISSUE's
    acceptance criterion."""
    B, Bp, _ = _bases(n_limbs)
    N = (B.M // 7) | 1
    while math.gcd(N, B.M * Bp.M) != 1:
        N += 2
    rng = random.Random(n_limbs)
    with backend(backend_name):
        mont = RNSMontgomery(B, Bp, N)
        for a, e in [(rng.randrange(1, N), rng.randrange(1 << 16)),
                     (rng.randrange(1, N), 0),
                     (rng.randrange(1, N), 1),
                     (N - 1, (1 << 16) - 1)]:
            assert mont.modexp(a, e) == pow(a, e, N), (a, e)


# ------------------------------------------------------- engine: crypto
def _ctx():
    return CryptoContext(n_limbs=4, exp_bits=16)


def _crypto_engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("crypto_slots", 2)
    kw.setdefault("crypto_ctx", _ctx())
    kw.setdefault("crypto_chunk", 4)
    return ContinuousBatcher(cfg, params, **kw)


def _crypto_reqs(ctx, seed=0, rid0=100):
    rng = random.Random(seed)
    MMp = ctx.baseB.M * ctx.baseBp.M

    def modulus():
        while True:
            N = rng.randrange(5, ctx.n_max) | 1
            if math.gcd(N, MMp) == 1:
                return N

    reqs, oracle = [], {}
    for i in range(3):
        N = modulus()
        a, e = rng.randrange(1, N), rng.randrange(1 << 16)
        reqs.append(CryptoRequest(rid=rid0 + i, op="modexp", a=a, b=e, n=N))
        oracle[rid0 + i] = pow(a, e, N)
    N = modulus()
    a, b = rng.randrange(1, N), rng.randrange(1, N)
    reqs.append(CryptoRequest(rid=rid0 + 3, op="modmul", a=a, b=b, n=N))
    oracle[rid0 + 3] = (a * b) % N
    a, d = rng.randrange(ctx.baseB.M), rng.randrange(1, ctx.baseB.M)
    reqs.append(CryptoRequest(rid=rid0 + 4, op="divmod", a=a, b=d))
    oracle[rid0 + 4] = divmod(a, d)
    return reqs, oracle


def test_engine_crypto_only_oracle_verify_and_no_retrace(cfg, params):
    eng = _crypto_engine(cfg, params, rns_verify=True)
    reqs, oracle = _crypto_reqs(eng.crypto_ctx)
    for r in reqs:
        eng.submit(r)
    done = eng.run_to_completion()
    assert sorted(r.rid for r in done) == sorted(oracle)
    for r in done:
        assert r.result == oracle[r.rid], (r.rid, r.op)
        assert eng.verify_log[r.rid] is True
        assert r.t_done is not None and r.t_admit is not None
    sizes = eng.jit_cache_sizes()
    for name in ("admit", "step", "final", "modmul", "divmod",
                 "fingerprint"):
        assert sizes[f"crypto_{name}"] == 1, sizes
    # slot churn: 3 modexps through 2 lane slots means at least one reuse
    by_slot = {}
    for r in done:
        if r.op == "modexp":
            by_slot.setdefault(r.slot_index, []).append(r.rid)
    assert set(by_slot) <= {0, 1}
    drained = eng.drain_completed()
    assert sorted(r.rid for r in drained) == sorted(oracle)
    assert eng.verify_log == {} and len(eng.crypto.completed) == 0


def test_engine_crypto_wire_corrupt_detect_and_repair(cfg, params):
    eng = _crypto_engine(cfg, params, rns_verify=True)
    ctx = eng.crypto_ctx
    N = 1000003
    assert math.gcd(N, ctx.baseB.M * ctx.baseBp.M) == 1
    eng.submit(CryptoRequest(rid=1, op="modexp", a=777, b=4321, n=N))
    eng.try_admit(0.0)   # slot bound, fingerprint published
    key = ("crypto", 1)
    assert eng.wire_ok(key)
    eng.corrupt_wire(key, channel=0, delta=5)
    assert not eng.wire_ok(key)               # detected by redundancy
    rep = eng.repair_wire(key)
    assert rep["repaired"] == 1 and rep["unrecoverable"] == 0
    assert eng.wire_ok(key)                   # located and corrected
    done = eng.run_to_completion()
    assert done[0].result == pow(777, 4321, N)
    assert eng.verify_log[1] is True          # retirement re-verified


def test_crypto_family_gating(cfg, params):
    # no crypto lane -> crypto submissions are refused with guidance
    eng = ContinuousBatcher(cfg, params, n_slots=2, cache_len=CACHE_LEN,
                            prefill_chunk=CHUNK)
    with pytest.raises(ValueError, match="crypto_slots"):
        eng.submit(CryptoRequest(rid=0, op="modexp", a=2, b=3, n=1000003))
    assert "crypto_admit" not in eng.jit_cache_sizes()
    # unknown family tag
    bad = Request(rid=1, prompt=[1, 2], max_new=2, family="audio")
    with pytest.raises(ValueError, match="unknown request family"):
        eng.submit(bad)
    # crypto_ctx without crypto_slots is a configuration error
    with pytest.raises(ValueError, match="crypto_slots"):
        ContinuousBatcher(cfg, params, n_slots=2, cache_len=CACHE_LEN,
                          prefill_chunk=CHUNK, crypto_ctx=_ctx())


def test_duplicate_rid_across_families_rejected(cfg, params):
    eng = _crypto_engine(cfg, params, rns_verify=True)
    eng.submit(Request(rid=7, prompt=[1, 2, 3], max_new=2))
    with pytest.raises(ValueError, match="rid 7"):
        eng.submit(CryptoRequest(rid=7, op="modexp", a=2, b=3, n=1000003))
    eng.submit(CryptoRequest(rid=8, op="modexp", a=2, b=3, n=1000003))
    with pytest.raises(ValueError, match="rid 8"):
        eng.submit(Request(rid=8, prompt=[1], max_new=1))


def test_context_and_lane_validation():
    ctx = _ctx()
    with pytest.raises(ValueError, match="unknown crypto op"):
        ctx.validate(CryptoRequest(rid=0, op="sqrt", a=1, b=1))
    with pytest.raises(ValueError, match="needs a modulus"):
        ctx.validate(CryptoRequest(rid=0, op="modexp", a=1, b=1))
    with pytest.raises(ValueError, match="must lie in"):
        ctx.validate(CryptoRequest(rid=0, op="modexp", a=1, b=1,
                                   n=ctx.n_max + 1))
    with pytest.raises(ValueError, match="coprime"):
        ctx.validate(CryptoRequest(rid=0, op="modexp", a=1, b=1,
                                   n=ctx.baseB.moduli[0] * 3))
    with pytest.raises(ValueError, match="exp_bits"):
        ctx.validate(CryptoRequest(rid=0, op="modexp", a=1,
                                   b=1 << ctx.exp_bits, n=1000003))
    with pytest.raises(ValueError, match="dynamic range"):
        ctx.validate(CryptoRequest(rid=0, op="divmod", a=ctx.baseB.M, b=1))
    with pytest.raises(ValueError, match="divide exp_bits"):
        CryptoLane(1, exp_bits=16, chunk=5)
    with pytest.raises(ValueError, match="BASE_MA or RRNS"):
        CryptoContext(n_limbs=3, layout=Layout.BASE)


# ------------------------------------------- mixed-workload isolation
def _llm_requests(cfg, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda rid, plen, max_new: Request(
        rid=rid, prompt=[int(t) for t in rng.integers(1, cfg.vocab, plen)],
        max_new=max_new,
    )
    return [mk(0, 5, 8), mk(1, 11, 7), mk(2, 3, 9)]


def _staggered_run(cfg, params, crypto_reqs):
    """The staggered-overlap harness with crypto traffic interleaved at
    fixed ticks: r0 streams alone, r1 joins mid-decode, then r2, with
    crypto admissions/laddering sharing every step() tick.  Returns the
    engine, the LLM requests and their K/V rows at retirement."""
    eng = ContinuousBatcher(
        cfg, params, n_slots=3, cache_len=CACHE_LEN, prefill_chunk=CHUNK,
        crypto_slots=2, crypto_ctx=_ctx(), crypto_chunk=4,
    )
    rows = snapshot_rows(eng)
    reqs = _llm_requests(cfg)
    eng.submit(reqs[0])
    if crypto_reqs:
        eng.submit(crypto_reqs[0])       # crypto rides along from tick 0
    eng.try_admit()
    eng.step(), eng.step()
    eng.submit(reqs[1])
    for c in crypto_reqs[1:]:
        eng.submit(c)
    eng.try_admit()
    eng.step()
    eng.submit(reqs[2])
    eng.try_admit()
    assert len(eng.sched.decoding_slots()) == 3
    while eng.busy:
        eng.try_admit()
        eng.step()
    return eng, reqs, rows


def test_mixed_workload_llm_bitwise_identical(cfg, params):
    """Crypto co-residency must be bitwise-invisible to the LLM lane:
    tokens AND the full KV trajectories of every request equal the
    crypto-free run's (the same compiled graphs on the same inputs), and
    both hold to the plain reference; the crypto results equal a
    crypto-only run's (isolation holds in both directions)."""
    crypto_reqs, oracle = _crypto_reqs(_ctx())
    mixed, mreqs, m_rows = _staggered_run(cfg, params, crypto_reqs)
    plain, preqs, p_rows = _staggered_run(cfg, params, [])
    m_out = {r.rid: list(r.out) for r in mixed.sched.completed}
    p_out = {r.rid: list(r.out) for r in plain.sched.completed}
    assert m_out == p_out and sorted(m_out) == [0, 1, 2]
    assert sorted(m_rows) == sorted(p_rows) == [0, 1, 2]
    for r in mreqs:
        for name in ("k", "v"):
            np.testing.assert_array_equal(m_rows[r.rid][name],
                                          p_rows[r.rid][name])
        check_against_reference(cfg, params, r, m_rows[r.rid])
    # crypto side: same results as a crypto-only engine (and the oracle)
    solo = _crypto_engine(cfg, params, n_slots=3)
    solo_reqs, _ = _crypto_reqs(solo.crypto_ctx)
    for r in solo_reqs:
        solo.submit(r)
    solo_res = {r.rid: r.result for r in solo.run_to_completion()}
    for r in mixed.crypto.completed:
        assert r.result == oracle[r.rid] == solo_res[r.rid]
    # co-residency never retraced either lane's graphs
    sizes = mixed.jit_cache_sizes()
    assert sizes["decode"] == 1 and sizes["crypto_step"] == 1


# ------------------------------------------------------- launcher family
def test_launcher_crypto_trace_roundtrip_and_families(tmp_path):
    from repro.launch.serve import main as serve_main

    trace = str(tmp_path / "mixed.serve-trace.jsonl")
    report = serve_main([
        "--arch", "gemma-2b", "--requests", "1", "--max-new", "2",
        "--slots", "2", "--cache-len", "64", "--arrival-rate", "0",
        "--crypto-slots", "1", "--crypto-requests", "3",
        "--crypto-limbs", "3", "--crypto-exp-bits", "8",
        "--crypto-chunk", "4", "--save-trace", trace,
    ])
    assert report["crypto"]["requests"] == 3
    assert report["crypto"]["oracle_failed"] == 0
    assert report["requests"] == 4
    lines = [json.loads(s) for s in open(trace)]
    fams = [d.get("family", "llm") for d in lines]
    assert fams.count("crypto") == 3 and fams.count("llm") == 1
    # big ints round-trip through hex strings
    assert all(isinstance(d["a"], str) for d in lines
               if d.get("family") == "crypto")
    replay = serve_main([
        "--arch", "gemma-2b", "--trace", trace, "--slots", "2",
        "--cache-len", "64", "--crypto-slots", "1", "--crypto-limbs", "3",
        "--crypto-exp-bits", "8", "--crypto-chunk", "4",
    ])
    assert replay["crypto"]["oracle_failed"] == 0
    assert replay["requests"] == 4
    # --families filters the replay; llm-only needs no crypto lane
    llm_only = serve_main([
        "--arch", "gemma-2b", "--trace", trace, "--slots", "2",
        "--cache-len", "64", "--families", "llm",
    ])
    assert llm_only["requests"] == 1 and "crypto" not in llm_only
    # crypto lines without --crypto-slots are refused with guidance
    with pytest.raises(SystemExit):
        serve_main(["--arch", "gemma-2b", "--trace", trace,
                    "--slots", "2", "--cache-len", "64"])


def test_launcher_rejects_cross_family_duplicate_rids(tmp_path):
    from repro.launch.serve import load_trace

    trace = tmp_path / "dup.jsonl"
    trace.write_text(
        '{"rid": 0, "prompt": [1, 2], "max_new": 2}\n'
        '{"rid": 0, "family": "crypto", "op": "modexp",'
        ' "a": "0x2", "b": 3, "n": 101}\n'
    )
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="duplicate rids"):
        load_trace(str(trace), rng, 100)
    bad = tmp_path / "fam.jsonl"
    bad.write_text('{"rid": 0, "family": "audio", "prompt": [1],'
                   ' "max_new": 1}\n')
    with pytest.raises(ValueError, match="unknown family"):
        load_trace(str(bad), rng, 100)
