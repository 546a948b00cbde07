"""Benchmark harness — one function per paper table/claim.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call is wall time per
logical operation on THIS host's CPU — correctness/trend data, not TPU
numbers; the TPU story lives in the dry-run roofline).  ``--json PATH``
additionally writes the same rows as machine-readable JSON (default
``BENCH_codec.json``) so the perf trajectory is trackable across PRs;
``--small`` shrinks every sweep for CI smoke runs.

  table1_opcount       paper Table 1: modular-mult counts, ours vs classic
  compare_latency      Alg.1 vs classic 2-MRC vs approx-CRT, batched, vs n
  compare_kernel       fused Pallas Alg.1 (interpret) vs unfused reference
  extension_methods    exactness + timing of MRC / Shenoy / Kawamura
  grad_codec           wire bytes + encode/allreduce/decode cost vs fp32
  codec_correct        RRNS detect vs locate-and-correct cost + wire tax
  rns_array_api        typed RnsArray frontend vs legacy dispatch (~0 cost)
  division_scaling     comparison-driven divmod / scaling costs
  serve_batching       continuous batching vs one-at-a-time serving
  serve_paged          paged prefix-sharing pool vs the monolithic cache
  serve_offline        saturation harness vs the synchronous tick driver
  ckpt_async           async RRNS checkpointer stall vs blocking saves
  crypto_modexp        batched crypto lane vs solo ladders, Pallas vs jnp

``--json`` also splits the ``rns_array_*`` rows into BENCH_api.json, the
``serve_*`` rows into BENCH_serve.json, the ``ckpt_*`` rows into
BENCH_ckpt.json, and the ``crypto_*`` rows into BENCH_crypto.json so the
typed-API overhead, the serving latency/throughput trajectory, the
checkpoint overlap, and the crypto-lane batching win each have their own
tracked artifact.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

import repro  # noqa: F401  (x64)
from repro.core import (
    approx_crt_ge,
    mrc,
    mrc_tree,
    classic_compare_ge,
    divmod_rns,
    extend_kawamura,
    extend_mrc,
    extend_shenoy,
    halve,
    make_base,
    pack,
    rns_compare_ge,
    rns_to_int,
)
from repro.dist.grad_codec import GradCodec
from repro.kernels import compare_op

NS = (4, 8, 16, 32, 64)
KERNEL_NS = (4, 8, 16)
MRC_NS = (16, 64, 128)
BATCH = 2048
ALLREDUCE_SIZES = (1 << 14, 1 << 18)
EXT_TRIALS = 512

RESULTS: dict[str, dict] = {}


def emit(name: str, us: float, derived) -> None:
    """One benchmark row: CSV to stdout, and into the --json record."""
    RESULTS[name] = {"us_per_call": float(us), "derived": str(derived)}
    print(f"{name},{us:.1f},{derived}")


def _time(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def _rand_operands(base, batch, rng):
    m = np.asarray(base.moduli_np)
    x1 = rng.integers(0, m, size=(batch, base.n)).astype(base.dtype)
    x2 = rng.integers(0, m, size=(batch, base.n)).astype(base.dtype)
    a1 = np.asarray([rns_to_int(base, r) % base.ma for r in x1], base.dtype)
    a2 = np.asarray([rns_to_int(base, r) % base.ma for r in x2], base.dtype)
    return (jnp.asarray(x1), jnp.asarray(a1), jnp.asarray(x2), jnp.asarray(a2))


# ---------------------------------------------------------------- Table 1
def _count_mults_ours(n):
    # MRC: n(n-1)/2, Alg.3 dot: n  (paper Table 1, row 1)
    return n * (n - 1) // 2 + n


def _count_mults_classic(n):
    return n * (n - 1)  # two MRCs (row 2)


def _instrumented_compare(base, N1, N2):
    """Pure-python Alg. 1 that counts modular multiplications."""
    n = base.n
    mults = 0
    z = [(a - b) % m for a, b, m in
         zip(base.residues_of(N1).tolist(), base.residues_of(N2).tolist(),
             base.moduli)]
    a = list(z)
    for i in range(1, n):
        for j in range(i):
            a[i] = (a[i] - a[j]) * int(base.inv_tri_np[j, i]) % base.moduli[i]
            mults += 1
    delta = 0
    for i in range(n):
        delta = (delta + a[i] * int(base.betas_ma_np[i])) % base.ma
        mults += 1
    dprime = (N1 % base.ma - N2 % base.ma) % base.ma
    assert (delta == dprime) == (N1 >= N2)
    return mults


def table1_opcount():
    rng = np.random.default_rng(0)
    for n in NS:
        base = make_base(n, bits=15)
        N1 = int(rng.integers(0, 1 << 60)) % base.M
        N2 = int(rng.integers(0, 1 << 60)) % base.M
        measured = _instrumented_compare(base, N1, N2)
        assert measured == _count_mults_ours(n), (measured, n)
        emit(f"table1_ours_n{n}", 0, measured)
        emit(f"table1_classic_n{n}", 0, _count_mults_classic(n))
        emit(f"table1_ratio_n{n}", 0,
             f"{_count_mults_classic(n) / measured:.3f}")


# ---------------------------------------------------------- compare latency
def compare_latency():
    rng = np.random.default_rng(1)
    for n in NS:
        base = make_base(n, bits=15)
        ops = _rand_operands(base, BATCH, rng)

        ours = jax.jit(lambda a, b, c, d: rns_compare_ge(base, a, b, c, d))
        classic = jax.jit(lambda a, c: classic_compare_ge(base, a, c))
        approx = jax.jit(lambda a, c: approx_crt_ge(base, a, c))

        t_ours = _time(ours, *ops)
        t_classic = _time(classic, ops[0], ops[2])
        t_approx = _time(approx, ops[0], ops[2])
        emit(f"compare_ours_n{n}", t_ours, f"{t_ours/BATCH*1e3:.2f}ns_elt")
        emit(f"compare_classic_n{n}", t_classic,
             f"speedup={t_classic/t_ours:.2f}")
        emit(f"compare_approx_n{n}", t_approx, "exact=False")


def compare_kernel():
    rng = np.random.default_rng(2)
    for n in KERNEL_NS:
        base = make_base(n, bits=15)
        ops = _rand_operands(base, 512, rng)
        fused = lambda a, b, c, d: compare_op(base, a, b, c, d, interpret=True)
        ref = jax.jit(lambda a, b, c, d: rns_compare_ge(base, a, b, c, d))
        t_f = _time(fused, *ops, iters=5)
        t_r = _time(ref, *ops, iters=5)
        ok = bool(jnp.all(fused(*ops) == ref(*ops)))
        emit(f"kernel_fused_interp_n{n}", t_f, f"match={ok}")
        emit(f"kernel_ref_jit_n{n}", t_r, "note=interpret-mode-not-perf")


def mrc_parallel_depth():
    """Sequential Alg. 2 vs divide-and-conquer MRC (the paper's §3.3
    parallel-time claim).  derived = dependency depth (levels of sequential
    modular ops on a machine with enough lanes)."""
    import math

    rng = np.random.default_rng(6)
    for n in MRC_NS:
        base = make_base(n, bits=15)
        m = np.asarray(base.moduli_np)
        xs = jnp.asarray(rng.integers(0, m, size=(256, n)).astype(np.int32))
        f_seq = jax.jit(lambda x: mrc(base, x))
        f_tree = jax.jit(lambda x: mrc_tree(base, x))
        assert bool(jnp.all(f_seq(xs) == f_tree(xs)))
        d_seq = n - 1
        d_tree = int(math.ceil(math.log2(n))) ** 2
        emit(f"mrc_seq_n{n}", _time(f_seq, xs, iters=5), f"depth={d_seq}")
        emit(f"mrc_tree_n{n}", _time(f_tree, xs, iters=5),
             f"depth~log2(n)^2={d_tree}")


# ------------------------------------------------------- extension methods
def extension_methods():
    rng = np.random.default_rng(3)
    n = 16
    base = make_base(n, bits=15)
    targets = (32603, 32587)
    trials = EXT_TRIALS
    Ns = [int(rng.integers(0, 1 << 62)) % base.M for _ in range(trials - 4)]
    Ns += [0, 1, base.M - 1, base.M - 2]  # adversarial edges
    xs = jnp.asarray(np.stack([base.residues_of(N) for N in Ns]))
    xr = jnp.asarray(np.asarray([N % base.ma for N in Ns], base.dtype))
    want = np.stack([[N % t for t in targets] for N in Ns])

    f_mrc = jax.jit(lambda x: extend_mrc(base, x, targets))
    f_sh = jax.jit(lambda x, r: extend_shenoy(base, x, r, base.ma, targets))
    f_kw = jax.jit(lambda x: extend_kawamura(base, x, targets))

    acc_mrc = float(np.mean(np.all(np.asarray(f_mrc(xs)) == want, -1)))
    acc_sh = float(np.mean(np.all(np.asarray(f_sh(xs, xr)) == want, -1)))
    acc_kw = float(np.mean(np.all(np.asarray(f_kw(xs)) == want, -1)))
    emit("extend_mrc", _time(f_mrc, xs), f"exact={acc_mrc:.4f}")
    emit("extend_shenoy", _time(f_sh, xs, xr), f"exact={acc_sh:.4f}")
    emit("extend_kawamura", _time(f_kw, xs), f"exact={acc_kw:.4f}")
    assert acc_mrc == 1.0 and acc_sh == 1.0  # exact methods must be exact


# --------------------------------------------------------------- grad codec
def grad_codec():
    from repro.kernels import codec_encode_op

    codec = GradCodec.make(world=512)
    rng = np.random.default_rng(4)
    g = jnp.asarray(rng.standard_normal((1 << 16,)).astype(np.float32))
    enc = jax.jit(codec.encode)
    enc_fused = jax.jit(lambda x: codec_encode_op(codec, x))
    dec = jax.jit(lambda p: codec.decode(codec.fold(p)))
    packed = enc(g)
    wire_bits = packed.shape[-1] * 16  # residues fit int16 lanes on the wire
    # Fair baseline: the codec provides EXACT integer summation over 512
    # replicas, whose scalar equivalent is int64 (int32 overflows, fp32 is
    # lossy/non-deterministic).  vs fp32 the wire costs 2x — recorded
    # honestly; the win is exactness + per-channel independence (paper §1).
    emit("codec_encode", _time(enc, g), f"wire_bits_per_elt={wire_bits}")
    bitwise = bool(jnp.all(enc_fused(g) == packed))
    emit("codec_encode_fused", _time(enc_fused, g), f"bitwise={bitwise}")
    emit("codec_decode", _time(dec, packed),
         f"vs_exact_int64_ratio={wire_bits/64:.2f},vs_fp32_ratio="
         f"{wire_bits/32:.2f}")
    err = float(jnp.max(jnp.abs(dec(packed) - g)))
    emit("codec_roundtrip", 0, f"max_err={err:.2e}(<2^-{codec.frac_bits})")


def grad_codec_allreduce():
    """End-to-end distributed path under shard_map over this host's 'data'
    axis, recorded at three granularities:

      allreduce_rns_*          per-tensor rns_psum, jnp codec (historical)
      allreduce_rns_fused_*    per-tensor rns_psum, fused Pallas codec
      allreduce_fp32_*         raw fp32 psum baseline
      allreduce_{fused,jnp}_decode_*  decode alone, fed the REAL post-psum
                               summed channels (not fresh encodings)
      allreduce_rns_per_leaf_* / allreduce_rns_tree_* / _tree_unfused_*
                               an 8-leaf pytree: one collective per leaf vs
                               the single-buffer bucketed psum
    """
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.dist.grad_codec import rns_psum, rns_psum_tree
    from repro.kernels import codec_decode_op

    ndev = len(jax.devices())
    world = max(ndev, 2)
    codec = GradCodec.make(world=world)                  # fused transport
    codec_jnp = GradCodec.make(world=world, fused=False)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    rng = np.random.default_rng(7)
    for size in ALLREDUCE_SIZES:
        g = jnp.asarray(rng.standard_normal(size).astype(np.float32))
        sm = lambda f: jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False,
        ))
        f_rns = sm(lambda x: rns_psum(codec_jnp, x, "data"))
        f_rns_fused = sm(lambda x: rns_psum(codec, x, "data"))
        f_fp = sm(lambda x: jax.lax.psum(x, "data") / ndev)
        t_rns = _time(f_rns, g, iters=10)
        t_rns_fused = _time(f_rns_fused, g, iters=10)
        t_fp = _time(f_fp, g, iters=10)
        err = float(jnp.max(jnp.abs(f_rns(g) - f_fp(g))))
        bitwise = bool(jnp.all(f_rns(g) == f_rns_fused(g)))
        emit(f"allreduce_rns_{size}", t_rns,
             f"elts_per_s={size/t_rns*1e6:.2e}")
        emit(f"allreduce_rns_fused_{size}", t_rns_fused,
             f"speedup_vs_jnp={t_rns/t_rns_fused:.2f},bitwise={bitwise}")
        emit(f"allreduce_fp32_{size}", t_fp,
             f"rns_overhead_x={t_rns_fused/t_fp:.2f},max_dev={err:.1e}")

        # decode alone, on the REAL post-psum summed channels (what the
        # optimizer-side decode actually sees — not fresh encodings)
        summed = sm(lambda x: jax.lax.psum(codec_jnp.encode(x), "data"))(g)
        f_fused_dec = jax.jit(lambda p: codec_decode_op(codec, p))
        f_jnp_dec = jax.jit(lambda p: codec_jnp.decode(codec_jnp.fold(p)))
        t_fused_dec = _time(f_fused_dec, summed, iters=10)
        t_jnp_dec = _time(f_jnp_dec, summed, iters=10)
        emit(f"allreduce_fused_decode_{size}", t_fused_dec,
             f"speedup_vs_jnp={t_jnp_dec/t_fused_dec:.2f}")
        emit(f"allreduce_jnp_decode_{size}", t_jnp_dec, "post-psum-input")

        # bucketing: an 8-leaf pytree as one collective per leaf vs ONE
        # single-buffer per-channel psum (tree_pack), fused and unfused
        tree = {
            f"leaf{i}": jnp.asarray(
                rng.standard_normal(size // 8).astype(np.float32)
            )
            for i in range(8)
        }
        smt = lambda f: jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False
        ))
        f_leaf = smt(lambda t: jax.tree_util.tree_map(
            lambda x: rns_psum(codec_jnp, x, "data"), t))
        f_tree = smt(lambda t: rns_psum_tree(codec, t, "data"))
        f_tree_u = smt(lambda t: rns_psum_tree(codec_jnp, t, "data"))
        t_leaf = _time(f_leaf, tree, iters=10)
        t_tree = _time(f_tree, tree, iters=10)
        t_tree_u = _time(f_tree_u, tree, iters=10)
        emit(f"allreduce_rns_per_leaf_{size}", t_leaf, "collectives=8")
        emit(f"allreduce_rns_tree_{size}", t_tree,
             f"collectives=1,speedup_vs_per_leaf={t_leaf/t_tree:.2f}")
        emit(f"allreduce_rns_tree_unfused_{size}", t_tree_u,
             f"collectives=1,fused_speedup={t_tree_u/t_tree:.2f}")


# ------------------------------------------------------------ codec correct
def codec_correct():
    """RRNS error handling on the wire buffer (DESIGN.md §10): the detect
    check (verify_packed, one MRC) vs the full locate-and-correct scan
    (n_channels survivor MRCs), and the wire tax of the second redundant
    channel.  Corruption is injected in ~1/1024 elements — repair must fix
    exactly those and leave the rest bitwise untouched."""
    codec = GradCodec.make(world=8, correct=True)
    rng = np.random.default_rng(8)
    B = min(ALLREDUCE_SIZES[-1], 1 << 14)
    g = jnp.asarray(rng.standard_normal(B).astype(np.float32))
    buf = codec.encode(g).astype(jnp.int32)
    m0 = int(codec.base.moduli[0])
    hits = rng.random(B) < 1.0 / 1024
    bad = jnp.where(
        jnp.asarray(hits)[:, None]
        & (jnp.arange(codec.n_channels) == 0),
        jnp.mod(buf + 7, m0), buf,
    )
    f_verify = jax.jit(lambda p: codec.verify_packed(p))
    f_correct = jax.jit(lambda p: codec.correct_packed(p))
    fixed, fault = f_correct(bad)
    n_fix = int(jnp.sum(fault >= 0))
    ok = bool(jnp.all(fixed == buf)) and n_fix == int(hits.sum())
    t_v = _time(f_verify, bad, iters=10)
    t_c = _time(f_correct, bad, iters=10)
    wire = codec.n_channels * 16  # int16-lane residues on the wire
    base_wire = (codec.base.n + 1) * 16
    emit("codec_verify_detect", t_v, f"elts={B}")
    emit("codec_locate_correct", t_c,
         f"vs_detect_x={t_c/t_v:.2f},repaired={n_fix},exact={ok}")
    emit("codec_correct_wire_bits", 0,
         f"per_elt={wire},vs_detect_only={wire/base_wire:.2f}x")
    assert ok, "RRNS repair must restore the corrupted buffer bitwise"


# ----------------------------------------------------------- typed frontend
def rns_array_api():
    """Dispatch overhead of the typed ``RnsArray`` frontend vs the legacy
    call signatures.  Under jit both routes trace to the same computation
    (the legacy functions ARE shims over the type), so steady-state time
    per call must be ~identical — this table guards that the API redesign
    stays free.  Rows land in BENCH_api.json for trend tracking."""
    from repro.core import RnsArray

    rng = np.random.default_rng(9)
    base = make_base(8, bits=15)
    ops = _rand_operands(base, BATCH, rng)
    a = RnsArray.from_parts(base, ops[0], ops[1])
    b = RnsArray.from_parts(base, ops[2], ops[3])
    legacy = jax.jit(lambda x1, a1, x2, a2: rns_compare_ge(base, x1, a1, x2, a2))
    typed = jax.jit(lambda u, v: u >= v)
    t_leg = _time(legacy, *ops)
    t_typ = _time(typed, a, b)
    bitwise = bool(jnp.all(typed(a, b) == legacy(*ops)))
    emit("rns_array_compare", t_typ,
         f"overhead_vs_legacy={t_typ/t_leg:.3f}x,bitwise={bitwise}")
    emit("rns_array_compare_legacy", t_leg, f"batch={BATCH}")

    base8 = make_base(4, bits=8)
    X = [int(rng.integers(1, base8.M)) for _ in range(8)]
    D = [int(rng.integers(1, x)) for x in X]
    xp = jnp.asarray(np.stack([np.concatenate(
        [base8.residues_of(v), [v % base8.ma]]).astype(np.int32) for v in X]))
    dp = jnp.asarray(np.stack([np.concatenate(
        [base8.residues_of(v), [v % base8.ma]]).astype(np.int32) for v in D]))
    ax = RnsArray.from_packed(base8, xp)
    ad = RnsArray.from_packed(base8, dp)
    f_leg = jax.jit(lambda p, q: divmod_rns(base8, p, q))
    f_typ = jax.jit(lambda u, v: u.divmod(v))
    t_leg = _time(f_leg, xp, dp, iters=5)
    t_typ = _time(f_typ, ax, ad, iters=5)
    ql, rl = f_leg(xp, dp)
    qt, rt = f_typ(ax, ad)
    bitwise = bool(jnp.all(ql == qt.to_packed()) and
                   jnp.all(rl == rt.to_packed()))
    emit("rns_array_divmod", t_typ,
         f"overhead_vs_legacy={t_typ/t_leg:.3f}x,bitwise={bitwise}")
    emit("rns_array_divmod_legacy", t_leg, "batch=8")


# --------------------------------------------------------------- serving
SERVE_REQS = 8
SERVE_PASSES = 3  # timed passes per engine; the gated ratio uses the best


def serve_batching():
    """Continuous batching (DESIGN.md §12) vs one-at-a-time serving on the
    smoke config: same workload (Poisson arrivals at tick rate 0.5), one
    engine with 4 slots vs a single-slot engine that can never overlap
    requests.  Rows land in BENCH_serve.json for trend tracking; tick
    latencies are deterministic, tok/s is this host's CPU."""
    from repro.configs import get_config
    from repro.launch.serve import simulate, synth_requests
    from repro.models import init_params
    from repro.serve.batcher import ContinuousBatcher

    cfg = get_config("gemma-2b").smoke()
    params = init_params(cfg, jax.random.key(0))

    def workload():
        rng = np.random.default_rng(12)
        return synth_requests(SERVE_REQS, rng, cfg.vocab, prompt_mean=8,
                              max_new=8, arrival_rate=0.5)

    def run(n_slots):
        eng = ContinuousBatcher(cfg, params, n_slots=n_slots, cache_len=32,
                                prefill_chunk=8)
        simulate(eng, workload())        # warmup: compile + one full pass
        n_warm = len(eng.sched.completed)
        t0 = time.perf_counter()
        counters = simulate(eng, workload())
        wall = time.perf_counter() - t0
        done = eng.sched.completed[n_warm:]  # only the timed pass counts
        toks = sum(len(r.out) for r in done)
        lat = float(np.mean([r.t_done - r.arrival for r in done]))
        return toks / wall, lat, counters["max_concurrency"]

    tokps_b, lat_b, conc = run(4)
    tokps_s, lat_s, _ = run(1)
    emit("serve_batched_tokps", 1e6 / tokps_b,
         f"tok_per_s={tokps_b:.1f},max_concurrency={conc}")
    emit("serve_solo_tokps", 1e6 / tokps_s, f"tok_per_s={tokps_s:.1f}")
    emit("serve_batching_speedup", 0,
         f"throughput_x={tokps_b/tokps_s:.2f},"
         f"latency_ticks_batched={lat_b:.1f},solo={lat_s:.1f}")


def serve_paged():
    """Paged prefix-sharing pool (DESIGN.md §13) vs the monolithic slot
    cache on the same workload: SERVE_REQS requests whose prompts share a
    75%-length common prefix (the system-prompt serving shape).  The
    committed gate metric is ``throughput_ratio`` — paged over monolithic
    tok/s on the SAME host, each the BEST of ``SERVE_PASSES`` timed passes
    (one noisy pass on a loaded CI runner must not fail the gate), so it
    tracks paging overhead machine-independently; ``pages_peak`` shows the
    dedup HBM win (shared prefix pages counted once, vs full rows for
    every slot)."""
    from repro.configs import get_config
    from repro.launch.serve import simulate
    from repro.models import init_params
    from repro.serve.batcher import ContinuousBatcher
    from repro.serve.scheduler import Request

    cfg = get_config("gemma-2b").smoke()
    params = init_params(cfg, jax.random.key(0))
    cache_len, page, chunk, plen, max_new = 32, 8, 8, 16, 8
    shared = plen * 3 // 4  # 75%-length common prefix

    def workload():
        rng = np.random.default_rng(21)
        prefix = [int(t) for t in rng.integers(1, cfg.vocab, shared)]
        return [
            Request(
                rid=i,
                prompt=prefix + [int(t) for t in
                                 rng.integers(1, cfg.vocab, plen - shared)],
                max_new=max_new, arrival=0.0,
            )
            for i in range(SERVE_REQS)
        ]

    def run(page_size):
        eng = ContinuousBatcher(
            cfg, params, n_slots=4, cache_len=cache_len,
            prefill_chunk=chunk, page_size=page_size,
        )
        simulate(eng, workload())        # warmup: compile + one full pass
        best = 0.0
        for _ in range(SERVE_PASSES):    # best-of-N rides out runner noise
            n_warm = len(eng.sched.completed)
            t0 = time.perf_counter()
            simulate(eng, workload())
            wall = time.perf_counter() - t0
            done = eng.sched.completed[n_warm:]
            toks = sum(len(r.out) for r in done)
            best = max(best, toks / wall)
        return best, eng

    tokps_p, eng_p = run(page)
    tokps_m, _ = run(None)
    st = eng_p.page_stats()
    emit("serve_paged_tokps", 1e6 / tokps_p,
         f"tok_per_s={tokps_p:.1f},pages_peak={st['pages_in_use_peak']},"
         f"dedup_hits={st['dedup_hits']},cow_copies={st['cow_copies']}")
    emit("serve_monolithic_tokps", 1e6 / tokps_m,
         f"tok_per_s={tokps_m:.1f}")
    emit("serve_paged_ratio", 0,
         f"throughput_ratio={tokps_p/tokps_m:.3f},"
         f"pages_peak={st['pages_in_use_peak']},"
         f"pages_monolithic_equiv={4 * (cache_len // page)}")


def serve_offline():
    """Saturation harness (DESIGN.md §16) vs the synchronous tick-clock
    driver on the same offline trace.  The harness pipeline = length-
    bucketed single-call prefill (ONE extend dispatch per prompt vs the
    baseline's ceil(plen/chunk) chunk loop) + a background completion
    pump running the detokenize callback (a sha256 over a 256 KiB
    payload per completion — releases the GIL like a real tokenizer's
    native code) off the driver thread; the baseline replays the
    identical trace through ``simulate()`` and runs the identical
    callback inline, serialized behind device work.  The gated metric is
    ``overlap_ratio`` — harness tok/s over baseline tok/s, each the
    best of SERVE_PASSES passes.  The floor holds machine-independently
    because the dispatch-count advantage alone clears it even on a
    single-core host (where threads cannot physically overlap); on
    multi-core runners the pump's overlap adds margin on top.  A second
    pass commits the same comparison over the PAGED, prefix-sharing
    pool (``offline_paged_*`` rows): bucketed prefill routes its pads
    through the §13 padded write barrier, and the harness must clear
    the same absolute 1.0 floor against the paged tick driver."""
    import hashlib

    from repro.configs import get_config
    from repro.launch.serve import simulate
    from repro.models import init_params
    from repro.serve.batcher import ContinuousBatcher
    from repro.serve.offline import OfflineInference
    from repro.serve.scheduler import Request

    cfg = get_config("gemma-2b").smoke()
    params = init_params(cfg, jax.random.key(0))
    cache_len, chunk, max_new = 64, 8, 8
    n = max(SERVE_REQS, 8)  # enough completions for the pump to matter
    payload = np.random.default_rng(3).bytes(256 << 10)

    def callback(req):
        return hashlib.sha256(payload).hexdigest()

    def workload(rid0):
        # prompts of 8..48 tokens: 1..6 chunk-loop dispatches baseline,
        # always exactly one bucketed dispatch on the harness
        rng = np.random.default_rng(17)
        return [
            Request(
                rid=rid0 + i,
                prompt=[int(t) for t in
                        rng.integers(1, cfg.vocab,
                                     8 + int(rng.integers(0, 41)))],
                max_new=max_new, arrival=0.0,
            )
            for i in range(n)
        ]

    harness = OfflineInference(
        cfg, params, n_slots=4, cache_len=cache_len, prefill_chunk=chunk,
        buckets=(16, 32, 64), overlap=True, queue_size=16,
        callback=callback,
    )
    harness.warmup()
    best_h, rep = 0.0, None
    for p in range(SERVE_PASSES):       # best-of-N rides out runner noise
        r = harness.run(workload(1000 * (p + 1)))
        if r["tok_per_s"] > best_h:
            best_h, rep = r["tok_per_s"], r
    harness.require_steady_state()

    eng = ContinuousBatcher(cfg, params, n_slots=4, cache_len=cache_len,
                            prefill_chunk=chunk)
    simulate(eng, workload(0))           # warmup: compile + one full pass
    [callback(r) for r in eng.sched.completed]
    best_s = 0.0
    for p in range(SERVE_PASSES):
        n_warm = len(eng.sched.completed)
        t0 = time.perf_counter()
        simulate(eng, workload(1000 * (p + 1)))
        done = eng.sched.completed[n_warm:]
        for r in done:                   # host work serialized, not overlapped
            callback(r)
        wall = time.perf_counter() - t0
        toks = sum(len(r.out) for r in done)
        best_s = max(best_s, toks / wall)

    bk = rep["buckets"]
    emit("offline_tokps", 1e6 / best_h,
         f"tok_per_s={best_h:.1f},"
         f"tok_per_s_per_chip={best_h / rep['n_chips']:.1f},"
         f"pump_max_depth={rep['overlap']['max_depth']},"
         f"pad_overhead={bk['pad_overhead']:.3f}")
    emit("offline_sync_tokps", 1e6 / best_s, f"tok_per_s={best_s:.1f}")
    emit("offline_overlap_ratio", 0,
         f"overlap_ratio={best_h / best_s:.3f},"
         f"retrace_free={int(rep['retrace_free'])}")

    # Paged-pool variant (DESIGN.md §13 x §16): the same saturation
    # pipeline over the paged, prefix-sharing pool — bucketed prefill
    # through the padded write barrier — vs the synchronous tick driver
    # on the SAME paged config and the same shared-prefix trace.  Half
    # the prompts share a two-page prefix so dedup actually fires.
    n_paged = 2 * n  # longer trace: steadier ratio, more completions
                     # for the pump to overlap against the tick driver

    def paged_workload(rid0):
        rng = np.random.default_rng(19)
        prefix = [int(t) for t in rng.integers(1, cfg.vocab, 16)]
        reqs = []
        for i in range(n_paged):
            plen = 8 + int(rng.integers(0, 41))
            body = [int(t) for t in rng.integers(1, cfg.vocab, plen)]
            if i % 2 and plen > 16:
                body = prefix + body[16:]
            reqs.append(Request(rid=rid0 + i, prompt=body,
                                max_new=max_new, arrival=0.0))
        return reqs

    # Denser ladder than the flat harness: a dedup hit on the shared
    # two-page prefix leaves a 1..8-token remainder to prefill (the 8
    # rung — padding that to 16 doubles the prefill FLOPs on exactly
    # the requests paging makes cheap), and the page-multiple middle
    # rungs keep the worst-case pad under one page for the rest.
    pharness = OfflineInference(
        cfg, params, n_slots=4, cache_len=cache_len, prefill_chunk=chunk,
        buckets=(8, 16, 24, 32, 40, 48, 64), overlap=True, queue_size=16,
        callback=callback, page_size=8,
    )
    pharness.warmup()
    best_p, prep = 0.0, None
    for p in range(SERVE_PASSES):
        r = pharness.run(paged_workload(1000 * (p + 1)))
        if r["tok_per_s"] > best_p:
            best_p, prep = r["tok_per_s"], r
    pharness.require_steady_state()

    peng = ContinuousBatcher(cfg, params, n_slots=4, cache_len=cache_len,
                             prefill_chunk=chunk, page_size=8)
    simulate(peng, paged_workload(0))    # warmup: compile + one full pass
    [callback(r) for r in peng.sched.completed]
    best_ps = 0.0
    for p in range(SERVE_PASSES):
        n_warm = len(peng.sched.completed)
        t0 = time.perf_counter()
        simulate(peng, paged_workload(1000 * (p + 1)))
        done = peng.sched.completed[n_warm:]
        for r in done:                   # host work serialized again
            callback(r)
        wall = time.perf_counter() - t0
        best_ps = max(best_ps, sum(len(r.out) for r in done) / wall)

    pg = prep["paging"][0]
    emit("offline_paged_tokps", 1e6 / best_p,
         f"tok_per_s={best_p:.1f},"
         f"dedup_hits={pg['dedup_hits']},"
         f"pad_overhead={prep['buckets']['pad_overhead']:.3f}")
    emit("offline_paged_overlap_ratio", 0,
         f"overlap_ratio={best_p / best_ps:.3f},"
         f"retrace_free={int(prep['retrace_free'])}")


# ------------------------------------------------------------ checkpointer
CKPT_STEPS = 6


def ckpt_async():
    """Async RRNS-coded checkpointing (DESIGN.md §14): per-step wall of a
    training loop saving EVERY step through the background Checkpointer vs
    blocking ``write_step_dir`` calls — same jitted compute, same tree.
    The committed gate metric is ``overlap_ratio`` = blocking/async wall,
    best of SERVE_PASSES passes: the async critical path replaces
    encode+fsync with a host-snapshot memcpy, so the ratio must stay
    >= 1.0 on any machine where the writer thread actually overlaps
    compute.  Rows land in BENCH_ckpt.json for trend tracking."""
    import shutil
    import tempfile

    from repro.train import checkpointer as cp

    rng = np.random.default_rng(13)
    tree = {
        f"w{i}": jnp.asarray(rng.standard_normal((1 << 15,)).astype(np.float32))
        for i in range(4)
    }  # 512 KiB of state -> ~2.5 MiB RRNS wire per step
    w = jnp.asarray(rng.standard_normal((512, 512)).astype(np.float32))

    @jax.jit
    def compute(x):  # stand-in train step, sized >= one write
        for _ in range(20):
            x = jnp.tanh(x @ x)
        return x

    jax.block_until_ready(compute(w))  # compile outside the timed region

    def blocking_pass(d):
        t0 = time.perf_counter()
        for s in range(1, CKPT_STEPS + 1):
            jax.block_until_ready(compute(w))
            cp.write_step_dir(d, s, tree)
        return (time.perf_counter() - t0) / CKPT_STEPS

    def async_pass(d):
        t0 = time.perf_counter()  # includes the close() drain: total wall
        with cp.Checkpointer(d, "1", queue_size=2) as saver:
            for s in range(1, CKPT_STEPS + 1):
                jax.block_until_ready(compute(w))
                saver.maybe_save(s, tree)
        return (time.perf_counter() - t0) / CKPT_STEPS

    def best_of(passes, fn):
        best = float("inf")
        for _ in range(passes):
            d = tempfile.mkdtemp(prefix="bench_ckpt_")
            try:
                best = min(best, fn(d))
            finally:
                shutil.rmtree(d, ignore_errors=True)
        return best

    t_block = best_of(SERVE_PASSES, blocking_pass)
    t_async = best_of(SERVE_PASSES, async_pass)
    emit("ckpt_blocking_step", t_block * 1e6, f"steps={CKPT_STEPS}")
    emit("ckpt_async_step", t_async * 1e6,
         f"speedup={t_block/t_async:.2f}")
    emit("ckpt_async_ratio", 0, f"overlap_ratio={t_block/t_async:.3f}")


# ----------------------------------------------------------------- crypto
CRYPTO_REQS = 8
CRYPTO_LIMBS = 4
CRYPTO_EXP_BITS = 16


def crypto_modexp():
    """Batched RNS modexp on the serve engine (DESIGN.md §15): the crypto
    lane with 4 slots (ladder chunks interleaved across co-resident
    requests through ONE jitted step graph) vs a 1-slot lane that must
    ladder requests back to back — same graphs, same workload, every
    result checked against ``pow()``.  The committed gate metric is
    ``throughput_ratio`` = batched/solo requests-per-second, each the
    best of SERVE_PASSES timed passes (runner-noise-proof like the serve
    and ckpt gates).  Also records one dual-base Montgomery product,
    pure-jnp vs the fused Pallas kernel (interpret mode off-TPU — a
    bitwise-identity row, not a perf row).  Rows land in
    BENCH_crypto.json."""
    import math
    import random

    from repro.configs import get_config
    from repro.core import backend
    from repro.core.array import RnsArray
    from repro.core.montgomery import DualRep, mont_mul
    from repro.models import init_params
    from repro.serve.batcher import ContinuousBatcher
    from repro.serve.crypto import CryptoContext, CryptoRequest

    ctx = CryptoContext(n_limbs=CRYPTO_LIMBS, exp_bits=CRYPTO_EXP_BITS)
    cfg = get_config("gemma-2b").smoke()
    params = init_params(cfg, jax.random.key(0))
    rng = random.Random(31)
    MMp = ctx.baseB.M * ctx.baseBp.M

    def modulus():
        while True:
            N = rng.randrange(5, ctx.n_max) | 1
            if math.gcd(N, MMp) == 1:
                return N

    cases = [(lambda N: (rng.randrange(1, N),
                         rng.randrange(1 << CRYPTO_EXP_BITS), N))(modulus())
             for _ in range(CRYPTO_REQS)]
    rid = iter(range(1, 1 << 30))  # fresh rids per pass (wire keys are held)

    def run(slots):
        eng = ContinuousBatcher(cfg, params, n_slots=1, cache_len=16,
                                prefill_chunk=8, crypto_slots=slots,
                                crypto_ctx=ctx, crypto_chunk=4)

        def one_pass():
            for a, e, N in cases:
                eng.submit(CryptoRequest(rid=next(rid), op="modexp",
                                         a=a, b=e, n=N))
            t0 = time.perf_counter()
            done = eng.run_to_completion()
            wall = time.perf_counter() - t0
            for r in done:
                assert r.result == pow(r.a, r.b, r.n), r.rid
            eng.drain_completed()
            return len(done) / wall

        one_pass()                  # warmup: compile admit/step/final
        return max(one_pass() for _ in range(SERVE_PASSES))

    rps_b = run(4)
    rps_s = run(1)
    emit("crypto_modexp_batched", 1e6 / rps_b,
         f"req_per_s={rps_b:.2f},slots=4,exp_bits={CRYPTO_EXP_BITS}")
    emit("crypto_modexp_solo", 1e6 / rps_s, f"req_per_s={rps_s:.2f}")
    emit("crypto_modexp_ratio", 0,
         f"throughput_ratio={rps_b/rps_s:.3f},reqs={CRYPTO_REQS}")

    # one Montgomery product, jnp vs fused Pallas, bitwise on all channels
    N = modulus()
    c = ctx.consts_for(N)

    def dual(vals):
        lo = np.stack([ctx.encode_lo(v) for v in vals])
        hi = np.stack([ctx.encode_hi(v) for v in vals])
        return DualRep(
            RnsArray.from_packed(ctx.baseB, jnp.asarray(lo, ctx.baseB.dtype),
                                 mb=ctx.mb),
            RnsArray.from_packed(ctx.baseBp, jnp.asarray(hi, ctx.baseBp.dtype)),
        )

    Bm = 256
    x = dual([rng.randrange(2 * N) for _ in range(Bm)])
    y = dual([rng.randrange(2 * N) for _ in range(Bm)])
    neg, n_hi = jnp.asarray(c["neg"]), jnp.asarray(c["n_hi"])
    with backend("jnp"):
        f_jnp = jax.jit(lambda u, v: mont_mul(u, v, neg, n_hi).lo.to_packed())
        t_j = _time(f_jnp, x, y, iters=5)
    with backend("pallas"):
        f_pal = jax.jit(lambda u, v: mont_mul(u, v, neg, n_hi).lo.to_packed())
        t_p = _time(f_pal, x, y, iters=5)
    bitwise = bool(jnp.all(f_jnp(x, y) == f_pal(x, y)))
    emit("crypto_mont_mul_jnp", t_j, f"batch={Bm},limbs={CRYPTO_LIMBS}")
    emit("crypto_mont_mul_pallas", t_p,
         f"bitwise={bitwise},note=interpret-mode-not-perf")
    assert bitwise, "Pallas Montgomery product diverged from the jnp path"


# --------------------------------------------------------- division/scaling
def division_scaling():
    base = make_base(4, bits=8)
    rng = np.random.default_rng(5)
    X = int(rng.integers(1, base.M))
    D = int(rng.integers(1, X))
    xp = pack(base, jnp.asarray(base.residues_of(X)), jnp.asarray(X % base.ma))
    dp = pack(base, jnp.asarray(base.residues_of(D)), jnp.asarray(D % base.ma))
    f_div = jax.jit(lambda a, b: divmod_rns(base, a, b))
    q, r = f_div(xp, dp)
    ok = (rns_to_int(base, np.asarray(q[..., :-1])),
          rns_to_int(base, np.asarray(r[..., :-1]))) == divmod(X, D)
    ncmp = 2 * base.M.bit_length() + 1
    emit("divmod_rns", _time(f_div, xp, dp, iters=5),
         f"comparisons={ncmp},correct={ok}")
    f_h = jax.jit(lambda a: halve(base, a))
    emit("scale_halve", _time(f_h, xp), "exact=True")


TABLES = [
    table1_opcount,
    compare_latency,
    compare_kernel,
    mrc_parallel_depth,
    extension_methods,
    grad_codec,
    grad_codec_allreduce,
    codec_correct,
    rns_array_api,
    serve_batching,
    serve_paged,
    serve_offline,
    ckpt_async,
    crypto_modexp,
    division_scaling,
]


def main(argv=None) -> None:
    global NS, KERNEL_NS, MRC_NS, BATCH, ALLREDUCE_SIZES, EXT_TRIALS, \
        SERVE_REQS, CKPT_STEPS, CRYPTO_REQS
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", nargs="?", const="BENCH_codec.json",
                    default=None, metavar="PATH",
                    help="also write rows as JSON (default BENCH_codec.json)")
    ap.add_argument("--json-api", default="BENCH_api.json", metavar="PATH",
                    help="with --json: where the rns_array_* rows (typed-API "
                         "dispatch overhead) are additionally written")
    ap.add_argument("--json-serve", default="BENCH_serve.json", metavar="PATH",
                    help="with --json: where the serve_* rows (continuous-"
                         "batching latency/throughput) are additionally "
                         "written")
    ap.add_argument("--json-ckpt", default="BENCH_ckpt.json", metavar="PATH",
                    help="with --json: where the ckpt_* rows (async "
                         "checkpoint overlap) are additionally written")
    ap.add_argument("--json-crypto", default="BENCH_crypto.json",
                    metavar="PATH",
                    help="with --json: where the crypto_* rows (batched "
                         "modexp lane throughput) are additionally written")
    ap.add_argument("--small", action="store_true",
                    help="CI smoke sizes: trimmed sweeps, same coverage")
    args = ap.parse_args(argv)
    if args.small:
        NS = (4, 8)
        KERNEL_NS = (4,)
        MRC_NS = (16,)
        BATCH = 256
        ALLREDUCE_SIZES = (1 << 12,)
        EXT_TRIALS = 64
        SERVE_REQS = 4
        CKPT_STEPS = 4
        CRYPTO_REQS = 4
    print("name,us_per_call,derived")
    for fn in TABLES:
        fn()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(RESULTS, f, indent=1, sort_keys=True)
        print(f"# wrote {len(RESULTS)} rows to {args.json}")
        api_rows = {k: v for k, v in RESULTS.items()
                    if k.startswith("rns_array_")}
        with open(args.json_api, "w") as f:
            json.dump(api_rows, f, indent=1, sort_keys=True)
        print(f"# wrote {len(api_rows)} rows to {args.json_api}")
        # serve_* = tick-clock engine rows, offline_* = saturation-harness
        # rows (DESIGN.md §16) — one committed trajectory file for both
        serve_rows = {k: v for k, v in RESULTS.items()
                      if k.startswith(("serve_", "offline_"))}
        with open(args.json_serve, "w") as f:
            json.dump(serve_rows, f, indent=1, sort_keys=True)
        print(f"# wrote {len(serve_rows)} rows to {args.json_serve}")
        ckpt_rows = {k: v for k, v in RESULTS.items()
                     if k.startswith("ckpt_")}
        with open(args.json_ckpt, "w") as f:
            json.dump(ckpt_rows, f, indent=1, sort_keys=True)
        print(f"# wrote {len(ckpt_rows)} rows to {args.json_ckpt}")
        crypto_rows = {k: v for k, v in RESULTS.items()
                       if k.startswith("crypto_")}
        with open(args.json_crypto, "w") as f:
            json.dump(crypto_rows, f, indent=1, sort_keys=True)
        print(f"# wrote {len(crypto_rows)} rows to {args.json_crypto}")


if __name__ == "__main__":
    main()
