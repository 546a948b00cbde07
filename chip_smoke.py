"""Bring-up check on a TPU: the paper's RNS kernels, the full-width server
with its crypto lane, and, with ``--chips 4``, the paths that exist only
across chips.

    python chip_smoke.py              # one chip: phases a, b and c
    python chip_smoke.py --chips 4    # the RNS all-reduce and 4 replicas

Everything runs in this one process through the program's own entry
functions (``repro.launch.serve.main``, ``repro.launch.train``,
``repro.core``); nothing here starts another process.  Phases on one chip:

a. every ``RnsArray`` operation that dispatches to a Pallas kernel runs
   under ``backend("pallas")`` and ``backend("jnp")`` on seeded operands:
   the results must be bitwise equal (exact integer arithmetic), and each
   compiled Pallas function must hold a ``tpu_custom_call``;
b. ``llama3.2-3b`` at its published widths (random weights from
   ``--seed``) served offline with RNS fingerprints and the crypto lane,
   then the engine's first-token logits against ``models.train_logits``;
c. peak device memory, compile time per phase, and the 64-bit ops left
   in the compiled decode step.

With ``--chips 4``: data-parallel ``mamba2-370m`` training steps with the
RNS all-reduce (forward and reversed device order) beside a plain fp32
psum, and four one-chip server replicas behind one admission queue.

Earlier lines print what each phase found; the last line is one JSON
object ``{"ok": true, "device": {...}}``.  Any failed check raises, so the
exit code is non-zero and no result is printed; so does a host without a
TPU.  The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` beside this file.
"""
from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro  # noqa: E402,F401  (x64 on, as every entry point has it)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SEED = 0
SERVE_ARCH, TRAIN_ARCH = "llama3.2-3b", "mamba2-370m"
FULL_WIDTH = True  # published widths; a CPU rehearsal flips it to --smoke
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 256


def width_flag() -> str:
    return "--no-smoke" if FULL_WIDTH else "--smoke"


def serve_args() -> list:
    return [
        "--arch", SERVE_ARCH, width_flag(), "--mode", "offline",
        "--page-size", "16", "--buckets", "pow2", "--slots", "4",
        "--cache-len", "1024", "--requests", "8", "--max-new", "16",
        "--rns-verify", "--crypto-slots", "4", "--crypto-requests", "8",
        "--crypto-limbs", "8", "--crypto-exp-bits", "32",
        "--seed", str(SEED),
    ]


# engine vs train_logits: both bf16 graphs over the same weights, so they
# agree to bf16 rounding carried through 28 layers; 16 bf16 ulps (2^-4
# relative) of the largest reference logit bounds that
LOGIT_RTOL = 2.0 ** -4
# RNS vs fp32 all-reduce: step 0 sees identical weights (only the loss
# pmean's rounding differs); later steps differ by the codec's 2^-16
# gradient quantisation as AdamW carries it into the weights
LOSS_ATOL_STEP0 = 1e-5
LOSS_ATOL = 1e-2


def say(msg: str) -> None:
    print(f"# {msg}", flush=True)


class CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.secs, self.hits, self.misses


def phase(name: str, log: CompileLog, times: dict):
    """Start phase ``name``; the returned ``done()`` records its wall and
    compile seconds, cache hits and peak memory, and prints them."""
    t0, (c0, h0, m0) = time.perf_counter(), log.snapshot()
    say(f"phase {name}: start")

    def done():
        c1, h1, m1 = log.snapshot()
        times[name] = {"wall_s": time.perf_counter() - t0,
                       "compile_s": c1 - c0, "cache_hits": h1 - h0,
                       "cache_misses": m1 - m0,
                       "peak_bytes_so_far": peak_memory()}
        say(f"phase {name}: passed {times[name]}")

    return done


def require(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def assert_mosaic(hlo: str, what: str) -> None:
    require("tpu_custom_call" in hlo,
            f"{what}: compiled Pallas function holds no tpu_custom_call")


# ------------------------------------------------------------ phase a


def _rns_operands(rng, base, count):
    """Seeded residue vectors of values in [0, M), with consistent m_a."""
    from repro.core import RnsArray, backend

    m = np.asarray(base.moduli_np)
    x = rng.integers(0, m, size=(count, base.n)).astype(np.int32)
    raw = np.concatenate([x, np.zeros((count, 1), np.int32)], axis=-1)
    with backend("jnp"):
        return RnsArray.from_packed(base, jnp.asarray(raw)).normalize()


def _mont_operands(rng, ctx, count):
    """Seeded Montgomery operands below per-row odd moduli N."""
    import math

    from repro.core import RnsArray
    from repro.core.montgomery import DualRep

    MMp = ctx.baseB.M * ctx.baseBp.M
    mods = []
    while len(mods) < 16:
        N = int.from_bytes(rng.bytes(16), "little") % ctx.n_max | 1
        if N > 4 and math.gcd(N, MMp) == 1:
            mods.append(N)
    Ns = [mods[i % len(mods)] for i in range(count)]

    def dual(vals):
        lo = np.stack([ctx.encode_lo(v) for v in vals])
        hi = np.stack([ctx.encode_hi(v) for v in vals])
        return DualRep(RnsArray.from_packed(ctx.baseB, jnp.asarray(lo)),
                       RnsArray.from_packed(ctx.baseBp, jnp.asarray(hi)))

    def below(N):
        return int.from_bytes(rng.bytes(16), "little") % N

    x = dual([below(N) for N in Ns])
    y = dual([below(N) for N in Ns])
    neg = jnp.asarray(np.stack([ctx.consts_for(N)["neg"] for N in Ns]))
    n_hi = jnp.asarray(np.stack([ctx.consts_for(N)["n_hi"] for N in Ns]))
    bit = jnp.asarray(rng.integers(0, 2, count).astype(np.int32))
    return x, y, neg, n_hi, bit


def kernel_cases(count: int = 4096):
    """(name, fn, args): every RnsArray-level route into a Pallas kernel."""
    from repro.core import make_base
    from repro.core.montgomery import ladder_step, mont_mul
    from repro.dist.grad_codec import GradCodec
    from repro.serve.crypto import CryptoContext

    rng = np.random.default_rng(SEED)
    base = make_base(8, bits=15)
    a = _rns_operands(rng, base, count)
    b = _rns_operands(rng, base, count)
    targets = (base.ma, 32717, 32713)
    codec = GradCodec.make(world=4)
    g = jnp.asarray((rng.standard_normal(count)
                     * np.exp(rng.uniform(-12, 6, count))).astype(np.float32))
    summed = sum(codec.encode(jnp.roll(g, k)).astype(jnp.int32)
                 for k in range(4))
    ctx = CryptoContext(n_limbs=8, exp_bits=32)
    x, y, neg, n_hi, bit = _mont_operands(rng, ctx, count // 2)
    return [
        ("compare", lambda p, q: p >= q, (a, b)),
        ("mrc", lambda p: p.to_mrs(), (a,)),
        ("extend", lambda p: p.extend(targets), (a,)),
        ("mul", lambda p, q: p * q, (a, b)),
        ("codec_encode", codec.encode_packed, (g,)),
        ("codec_decode", codec.decode_summed, (summed,)),
        ("mont_mul", mont_mul, (x, y, neg, n_hi)),
        ("ladder_step", ladder_step, (x, y, bit, neg, n_hi)),
    ]


def run_kernels() -> None:
    from repro.core import backend

    for name, fn, args in kernel_cases():
        outs = {}
        for route in ("pallas", "jnp"):
            with backend(route):
                # a fresh function per route: the route is read at trace
                # time, and jit's trace cache keys on the function
                compiled = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
            if route == "pallas":
                assert_mosaic(compiled.as_text(), name)
            outs[route] = jax.tree_util.tree_leaves(compiled(*args))
        same = all(np.array_equal(np.asarray(p), np.asarray(j))
                   for p, j in zip(outs["pallas"], outs["jnp"]))
        require(len(outs["pallas"]) == len(outs["jnp"]) and same,
                f"{name}: Pallas route differs from the jnp route")
        n = int(np.asarray(outs["pallas"][0]).size)
        say(f"kernel {name}: pallas == jnp bitwise over {n} outputs, "
            f"tpu_custom_call present")


# ------------------------------------------------------------ phase b


def check_serve_report(report: dict, *, replicas: int = 1) -> None:
    want = 8 + 8
    require(report["requests"] == want,
            f"served {report['requests']} of {want} requests")
    require(report["llm_requests"] == 8 and report["crypto_requests"] == 8,
            "request mix")
    require(report["rns"]["slots_failed"] == 0,
            f"rns verify failures: {report['rns']}")
    require(report["crypto"]["oracle_failed"] == 0,
            f"crypto oracle failures: {report['crypto']}")
    require(report["replicas"] == replicas, "replica count")
    say(f"serve: {report['requests']} requests, "
        f"{report['tokens_out']} tokens, steady state held, "
        f"rns {report['rns']}, crypto oracle_ok "
        f"{report['crypto']['oracle_ok']}/{report['crypto']['requests']}, "
        f"tok/s {report['tok_per_s']}, n_chips {report['n_chips']}, "
        f"replica_devices {report['replica_devices']}")


def run_server() -> dict:
    from repro.launch import serve

    report = serve.main(serve_args())  # require_steady_state() runs inside
    check_serve_report(report)
    return report


def run_logit_check() -> int:
    """Engine first-token logits vs ``models.train_logits`` on the same
    weights; returns the count of 64-bit ops in the compiled decode."""
    from repro.launch import serve
    from repro.models import decode_step, train_logits
    from repro.serve.batcher import ContinuousBatcher
    from repro.serve.offline import pow2_buckets

    cfg = serve.serving_config(SERVE_ARCH, smoke=not FULL_WIDTH)
    params = serve.serving_params(cfg, SEED)
    reqs = serve.synth_requests(8, np.random.default_rng(SEED), cfg.vocab,
                                prompt_mean=16, max_new=1, arrival_rate=0.25)
    eng = ContinuousBatcher(cfg, params, n_slots=4, cache_len=1024,
                            prefill_buckets=pow2_buckets(1024), page_size=16)
    rows = {}
    sample = eng._first_token
    for r in reqs:  # one at a time, so that each first token is r's
        def keep(logits, rid=r.rid):
            rows[rid] = logits[0, 0]
            return sample(logits)
        eng._first_token = keep
        eng.submit(r)
        eng.run_to_completion()
    done = {r.rid: r for r in eng.sched.completed}
    require(len(done) == 8, "logit check: requests did not finish")

    L = max(len(r.prompt) for r in reqs)
    toks = np.zeros((len(reqs), L), np.int32)
    for i, r in enumerate(reqs):
        toks[i, :len(r.prompt)] = r.prompt  # right pads: causally unseen
    ref_all, _ = jax.jit(lambda p, t: train_logits(cfg, p, {"tokens": t}))(
        params, jnp.asarray(toks))
    last = jnp.asarray([len(r.prompt) - 1 for r in reqs])
    ref = np.asarray(ref_all[jnp.arange(len(reqs)), last], np.float32)
    got = np.stack([np.asarray(rows[r.rid], np.float32) for r in reqs])
    tol = LOGIT_RTOL * float(np.abs(ref).max())
    diff = float(np.abs(got - ref).max())
    say(f"logits: engine vs train_logits max |diff| {diff} "
        f"(tolerance {tol}, largest |ref| {float(np.abs(ref).max())})")
    require(diff <= tol, "engine logits outside the stated tolerance")
    top2 = np.sort(ref, axis=-1)[:, -2:]
    for i, r in enumerate(reqs):
        if top2[i, 1] - top2[i, 0] > tol:
            require(done[r.rid].out[0] == int(ref[i].argmax()),
                    f"rid {r.rid}: greedy token disagrees with train_logits")
    say(f"logits: greedy first token agrees where the reference margin "
        f"exceeds the tolerance ({int((top2[:, 1] - top2[:, 0] > tol).sum())}"
        f" of {len(reqs)} prompts)")

    ps = eng.page_size
    hlo = jax.jit(
        lambda p, c, t, pos, pg: decode_step(cfg, p, c, t, pos, pages=pg,
                                             page_size=ps)
    ).lower(eng.params, eng.cache, jnp.zeros((4, 1), jnp.int32),
            jnp.zeros((4,), jnp.int32),
            jnp.asarray(eng.sched.table, jnp.int32)).compile().as_text()
    return len(re.findall(r"= (?:s64|f64)\[", hlo))


# ------------------------------------------------------------ --chips 4


def run_allreduce() -> None:
    from repro.configs import get_config
    from repro.dist.grad_codec import GradCodec
    from repro.launch import train
    from repro.models import init_params
    from repro.train.data import SyntheticLM
    from repro.train.optimizer import AdamWConfig, adamw_init

    steps, batch, seq = TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ
    res = train.main(["--arch", TRAIN_ARCH, width_flag(), "--rns-allreduce",
                      "--steps", str(steps), "--batch", str(batch),
                      "--seq", str(seq)])
    cfg = get_config(TRAIN_ARCH)
    if not FULL_WIDTH:
        cfg = cfg.smoke()
    opt_cfg = AdamWConfig(warmup=5, decay_steps=max(steps, 10))
    loader = SyntheticLM(cfg, seq=seq, batch=batch)
    codec = GradCodec.make(world=len(jax.devices()))

    def run(built):  # the loop train.main runs, without its extras
        step_fn, mesh = built
        params = init_params(cfg, jax.random.key(0))
        params, opt = train.replicate((params, adamw_init(params)), mesh)
        losses = []
        for s in range(steps):
            b = jax.tree_util.tree_map(jnp.asarray, loader.batch_at(s))
            params, opt, m = step_fn(params, opt, b)
            losses.append(float(m["loss"]))
        # compared on the host: each chip's memory goes to the next step
        return jax.device_get(params), losses

    def bitwise(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in
                   zip(jax.tree_util.tree_leaves(a),
                       jax.tree_util.tree_leaves(b)))

    main_params = jax.device_get(res.pop("params"))
    fwd, losses = run(train.make_dp_step(cfg, opt_cfg, codec))
    require(bitwise(main_params, fwd),
            "make_dp_step loop does not reproduce train.main")
    rev, rev_losses = run(train.make_dp_step(
        cfg, opt_cfg, codec, devices=jax.devices()[::-1]))
    require(bitwise(fwd, rev),
            "RNS all-reduce result depends on the device order")
    fp32_losses = run(train.make_dp_step(cfg, opt_cfg))[1]
    say(f"allreduce: train.main --rns-allreduce losses {res['losses']}")
    say(f"allreduce: rns {losses}, rns on reversed devices {rev_losses} "
        f"(weights bitwise equal), fp32 psum {fp32_losses}")
    drift = [abs(a - b) for a, b in zip(losses, fp32_losses)]
    say(f"allreduce: |rns - fp32| loss per step {drift} (bounds "
        f"{LOSS_ATOL_STEP0} at step 0, {LOSS_ATOL} after)")
    bounds = [LOSS_ATOL_STEP0] + [LOSS_ATOL] * (len(drift) - 1)
    # written so that a NaN on either side fails the comparison
    require(all(d <= b for d, b in zip(drift, bounds)),
            "RNS all-reduce losses drift from the fp32 psum")


def run_replicas() -> None:
    from repro.launch import serve

    # one prefill width instead of the pow2 ladder: each replica compiles
    # every graph for its own chip, and four cold ladders cost four times
    # the one-chip warmup; prompts longer than 32 take the chunk loop at
    # the same width
    report = serve.main(serve_args() + ["--replicas", "4", "--buckets", "32"])
    check_serve_report(report, replicas=4)
    devs = report["replica_devices"]
    require(report["n_chips"] == 4, f"n_chips {report['n_chips']}")
    require(all(len(d) == 1 for d in devs)
            and len({d[0] for d in devs}) == 4,
            f"replicas not one per device: {devs}")


# ------------------------------------------------------------ main


def peak_memory() -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-c on one chip; 4: only the paths "
                         "that exist across chips")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    require(len(jax.devices()) == args.chips,
            f"--chips {args.chips} but JAX sees {len(jax.devices())}")
    say(f"device {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {enable_compile_cache()}")
    log, times = CompileLog(), {}

    if args.chips == 4:
        done = phase("allreduce", log, times)
        run_allreduce()
        done()
        gc.collect()
        done = phase("replicas", log, times)
        run_replicas()
        done()
    else:
        done = phase("a_kernels", log, times)
        run_kernels()
        done()
        done = phase("b_server", log, times)
        run_server()
        done()
        gc.collect()  # the served weights go before the check's copy
        done = phase("b_logits", log, times)
        n64 = run_logit_check()
        done()
        say(f"decode step: {n64} s64/f64 ops in the compiled program")
    say(f"peak memory_stats bytes per device: {peak_memory()}")
    say(f"compile seconds per phase: "
        f"{ {k: v['compile_s'] for k, v in times.items()} }")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
