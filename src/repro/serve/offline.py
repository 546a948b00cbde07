# Copyright 2026 the repro authors
#
# Saturation-grade offline inference harness (DESIGN.md §16).
#
# ``launch/serve.py --mode sim`` replays traces on a single-threaded tick
# clock: it measures the ENGINE, never the system.  This module is the
# MLPerf-offline-style measurement layer on top of the PR 5-8 engine,
# modeled on maxtext's ``OfflineInference``:
#
#   * ``OfflineInference`` — wall-clock driver over one or more
#     ``ContinuousBatcher`` replicas.  ``warmup()`` pre-compiles every
#     (bucket, family) graph BEFORE timing starts; ``run()`` then replays
#     a workload under the real clock and asserts steady state added zero
#     retraces.
#   * ``CompletionPump`` — ONE background detokenize/callback thread fed
#     by a bounded queue, so host-side completion work overlaps the
#     persistent jitted decode step.  First-error-wins propagation
#     exactly like ``train/checkpointer.py``: a failed callback surfaces
#     on the next ``put()`` / ``flush()`` / ``close()``, never silently.
#   * ``ReplicaSet`` — data-parallel engine replicas behind ONE shared
#     admission deque; a request is dispatched to the least-loaded
#     replica with free capacity for its family.  ``replica_meshes``
#     carves the device fleet into per-replica meshes when it divides
#     evenly (on a single-device host every replica shares the device —
#     still useful as a scheduling test vehicle, reported as 1 chip).
#
# The closed-loop QPS search that drives this harness to saturation
# lives in ``serve/loadgen.py``.

from __future__ import annotations

import math
import queue
import threading
import time

import numpy as np

import jax

from repro.dist.sharding import auto_mesh

__all__ = [
    "CompletionPump",
    "OfflineInference",
    "ReplicaSet",
    "default_callback",
    "pow2_buckets",
    "replica_meshes",
    "sample_stats",
]


def sample_stats(xs) -> dict:
    """n/mean/p50/p95/p99 summary of a sample list.

    An empty sample returns the explicit ``n: 0`` record (all stats 0.0)
    instead of crashing ``np.percentile`` on ``[]`` — a family filter
    that leaves zero completed requests must not kill report generation.
    """
    if not xs:
        return {"n": 0, "mean": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}
    a = np.asarray(xs, np.float64)
    return {
        "n": int(a.size),
        "mean": float(a.mean()),
        "p50": float(np.percentile(a, 50)),
        "p95": float(np.percentile(a, 95)),
        "p99": float(np.percentile(a, 99)),
    }


def pow2_buckets(cache_len: int, lo: int = 8) -> tuple[int, ...]:
    """Power-of-two prefill buckets ``lo, 2*lo, ... , cache_len`` (the
    default bucket ladder of ``--mode offline``).  ``cache_len`` itself
    is appended when it is not a power of two so every admissible prompt
    hits a bucket (widths > 512 are multiples of 512 whenever cache_len
    is, per the engine's flash-chunk rule)."""
    if cache_len < 1:
        raise ValueError("cache_len must be >= 1")
    lo = max(1, min(lo, cache_len))
    out = []
    b = 1 << (lo - 1).bit_length()
    while b < cache_len:
        out.append(b)
        b <<= 1
    out.append(cache_len)
    return tuple(out)


def default_callback(req) -> str:
    """Minimal "detokenize": completed crypto requests render their
    big-int result, LLM requests their output token ids.  Real servers
    swap in a tokenizer's ``decode`` — anything swapped in runs on the
    pump thread, overlapped with device decode."""
    if getattr(req, "family", "llm") == "crypto":
        return f"{req.op}:{req.result}"
    return " ".join(str(t) for t in req.out)


class CompletionPump:
    """Background completion/detokenize thread behind a bounded queue.

    ``put(req)`` enqueues a retired request for the worker to run
    ``callback(req)`` on; the driver thread returns to stepping the
    engine immediately unless the queue is full (bounded = backpressure:
    a slow callback eventually throttles the producer instead of growing
    an unbounded buffer).  Results land in ``completed`` in submission
    order (single worker = FIFO).

    Error contract (the ``train/checkpointer.py`` pattern): the FIRST
    callback exception is held and re-raised from the next ``put()`` /
    ``flush()`` / ``close()`` — never dropped, no silent hang.  After an
    error the worker keeps draining the queue (dropping items) so a
    producer blocked on a full queue always unblocks.
    """

    _SENTINEL = object()

    def __init__(self, callback, *, queue_size: int = 64):
        if queue_size < 1:
            raise ValueError("queue_size must be >= 1")
        self._callback = callback
        self._q: queue.Queue = queue.Queue(maxsize=queue_size)
        self.completed: list = []  # (request, callback result), FIFO
        self._error: BaseException | None = None
        self._error_lock = threading.Lock()
        self._closed = False
        self.processed = 0
        self.dropped = 0  # items drained after the first error
        self.max_depth = 0
        self.blocked_puts = 0  # puts that found the queue full
        self._thread = threading.Thread(
            target=self._worker, daemon=True, name="completion-pump"
        )
        self._thread.start()

    # -- lifecycle ---------------------------------------------------------

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        # don't mask an in-flight exception with the held one: it already
        # surfaced (or will, from the caller's own flush/close)
        self.close(raise_error=exc[0] is None)

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is self._SENTINEL:
                self._q.task_done()
                return
            try:
                if self._error is not None:
                    self.dropped += 1  # drain-after-error: never deadlock
                    continue
                self.completed.append((item, self._callback(item)))
                self.processed += 1
            except BaseException as e:
                with self._error_lock:
                    if self._error is None:  # first failure wins
                        self._error = e
            finally:
                self._q.task_done()

    def _check_error(self) -> None:
        with self._error_lock:
            err, self._error = self._error, None
        if err is not None:
            raise err

    # -- producing ---------------------------------------------------------

    def put(self, req) -> None:
        """Enqueue one retired request; blocks when the queue is full
        (backpressure); re-raises the first worker error if any."""
        self._check_error()
        if self._closed:
            raise RuntimeError("CompletionPump is closed")
        if self._q.full():
            self.blocked_puts += 1
        self._q.put(req)  # blocks when full
        self.max_depth = max(self.max_depth, self._q.qsize())

    def flush(self) -> None:
        """Block until every enqueued completion has run; re-raise the
        first worker error if any callback failed."""
        self._q.join()
        self._check_error()

    def close(self, *, raise_error: bool = True) -> None:
        """Idempotent: stop the worker and join it.  With ``raise_error``
        (default) the held error surfaces here; pass False on exception
        paths where another error is already propagating."""
        if not self._closed:
            self._closed = True
            self._q.put(self._SENTINEL)
            self._thread.join()
        if raise_error:
            self._check_error()

    def stats(self) -> dict:
        return {
            "queue_size": self._q.maxsize,
            "processed": self.processed,
            "dropped": self.dropped,
            "max_depth": self.max_depth,
            "blocked_puts": self.blocked_puts,
        }


def replica_meshes(n: int, devices=None) -> list:
    """Carve the device fleet into ``n`` per-replica 1-axis meshes.

    Returns ``n`` ``Mesh(("data",))`` objects, one per equal share of the
    fleet.  A one-device host returns ``n`` Nones instead (every replica on
    that device — the CPU case, where replicas still exercise the
    shared-admission scheduling protocol); any other fleet that ``n`` does
    not divide is refused rather than silently stacked on one device."""
    if n < 1:
        raise ValueError("need >= 1 replica")
    devs = list(jax.devices()) if devices is None else list(devices)
    if len(devs) == 1:
        return [None] * n
    if len(devs) % n:
        fits = [d for d in range(1, len(devs) + 1) if len(devs) % d == 0]
        raise ValueError(f"{n} replicas cannot split {len(devs)} devices "
                         f"evenly; replica counts that do: {fits}")
    per = len(devs) // n
    return [auto_mesh((per,), ("data",), devices=devs[i * per:(i + 1) * per])
            for i in range(n)]


class ReplicaSet:
    """Data-parallel engine replicas behind ONE shared admission deque.

    ``submit`` parks requests in arrival order; ``pump(now)`` dispatches
    each to the least-loaded replica that has free capacity for its
    family (LLM: FREE slots beyond the engine's own backlog; crypto
    modexp: FREE lane slots beyond queued ladders; crypto one-shots:
    round-robin — they execute inside admission and never bind a slot).
    A request whose family has no capacity anywhere stays parked; FIFO
    is preserved WITHIN each family (capacity is family-wide, so a
    later same-family request can never jump an earlier one).
    """

    def __init__(self, engines: list):
        if not engines:
            raise ValueError("need >= 1 engine replica")
        self.engines = list(engines)
        self.queue: list = []  # shared admission queue (arrival order)
        self.steps = 0  # total engine decode/ladder steps across replicas
        self.dispatched = [0] * len(engines)
        self._rr = 0  # one-shot round-robin cursor
        # fingerprint verdicts harvested at retirement (the engines pop
        # their verify logs when drained, so the set keeps the tally)
        self.verify_ok = 0
        self.verify_failed = 0

    # -- capacity probes ---------------------------------------------------

    @staticmethod
    def _free_llm(eng) -> int:
        free = sum(1 for s in eng.sched.slots if s.state == "FREE")
        return free - len(eng.sched.queue)

    @staticmethod
    def _free_modexp(eng) -> int:
        if eng.crypto is None:
            return 0
        free = sum(1 for s in eng.crypto.slots if s.state == "FREE")
        queued = sum(1 for r in eng.crypto.queue if r.op == "modexp")
        return free - queued

    # -- shared-queue protocol ---------------------------------------------

    def submit(self, req) -> None:
        self.queue.append(req)

    def pump(self, now: float) -> int:
        """One dispatch pass over the shared queue; returns how many
        requests were handed to a replica.  ``now`` is threaded through
        for symmetry with the engine API (dispatch itself stamps
        nothing — admission stamps ``t_admit``)."""
        del now
        placed, rest = 0, []
        for req in self.queue:
            family = getattr(req, "family", "llm")
            ei = self._pick(family, req)
            if ei is None:
                rest.append(req)
                continue
            self.engines[ei].submit(req)
            self.dispatched[ei] += 1
            placed += 1
        self.queue = rest
        return placed

    def _pick(self, family: str, req) -> int | None:
        if family == "crypto":
            armed = [i for i, e in enumerate(self.engines)
                     if e.crypto is not None]
            if not armed:
                raise ValueError(
                    "crypto-family request but no replica has a crypto "
                    "lane; build engines with crypto_slots >= 1"
                )
            if req.op != "modexp":
                # one-shots execute inside admission: spread round-robin
                self._rr += 1
                return armed[self._rr % len(armed)]
            best = max(armed, key=lambda i: self._free_modexp(
                self.engines[i]))
            return best if self._free_modexp(self.engines[best]) > 0 \
                else None
        best = max(range(len(self.engines)),
                   key=lambda i: self._free_llm(self.engines[i]))
        return best if self._free_llm(self.engines[best]) > 0 else None

    # -- stepping ----------------------------------------------------------

    @property
    def stepping(self) -> bool:
        """Any replica has device work this instant (decoding rows or
        running ladders) — False means the set is idle waiting on
        arrivals or free capacity."""
        return any(
            e.sched.decoding_slots()
            or (e.crypto is not None and e.crypto.running_slots())
            for e in self.engines
        )

    @property
    def busy(self) -> bool:
        return bool(self.queue) or any(e.busy for e in self.engines)

    def step_all(self, now: float) -> list:
        """Admit + one decode/ladder step on every replica with work;
        returns the requests (all families, all replicas) that retired."""
        retired = []
        for eng in self.engines:
            eng.try_admit(now)
            if eng.sched.decoding_slots() or (
                eng.crypto is not None and eng.crypto.running_slots()
            ):
                eng.step(now)
                self.steps += 1
            if eng.rns_verify:
                # harvest before drain_completed pops the log entries
                for ok in eng.verify_log.values():
                    self.verify_ok += bool(ok)
                    self.verify_failed += not ok
            retired.extend(eng.drain_completed())
        return retired


class OfflineInference:
    """Wall-clock saturation harness over data-parallel engine replicas.

    Lifecycle: construct -> ``warmup()`` (pre-compiles every (bucket,
    family) graph and snapshots the jit-cache census) -> ``run(reqs)``
    one or more times (timed; asserts zero steady-state retraces via
    ``require_steady_state``).  Engine kwargs mirror
    ``ContinuousBatcher``; ``buckets`` arms length-bucketed single-call
    prefill, ``overlap`` routes completions through a
    ``CompletionPump`` instead of running the callback inline on the
    driver thread.  Every replica serves from its own paged,
    prefix-sharing pool; buckets compose with it through the padded write
    barrier (DESIGN.md §13), and warmup additionally pre-compiles the
    copy-on-write graph so steady state stays retrace-free.
    """

    def __init__(self, cfg, params, *, n_slots: int, cache_len: int,
                 prefill_chunk: int = 32,
                 buckets: tuple | None = None,
                 replicas: int = 1,
                 overlap: bool = True,
                 queue_size: int = 64,
                 callback=None,
                 rns_verify: bool = False,
                 page_size: int = 16, n_pages: int | None = None,
                 crypto_slots: int = 0, crypto_ctx=None,
                 crypto_chunk: int = 8):
        from repro.serve.batcher import ContinuousBatcher

        self.meshes = replica_meshes(replicas)
        self.engines = [
            ContinuousBatcher(
                cfg, params, n_slots=n_slots, cache_len=cache_len,
                prefill_chunk=prefill_chunk, prefill_buckets=buckets,
                rns_verify=rns_verify, mesh=mesh,
                page_size=page_size, n_pages=n_pages,
                crypto_slots=crypto_slots, crypto_ctx=crypto_ctx,
                crypto_chunk=crypto_chunk,
            )
            for mesh in self.meshes
        ]
        self.replica_set = ReplicaSet(self.engines)
        self.cache_len = int(cache_len)
        self.buckets = self.engines[0].prefill_buckets
        self.overlap = bool(overlap)
        self.queue_size = int(queue_size)
        self.callback = callback if callback is not None else \
            default_callback
        devs = set()
        for mesh in self.meshes:
            devs.update(mesh.devices.flat if mesh is not None
                        else [jax.devices()[0]])
        self.n_chips = len(devs)
        self._warm_sizes: list[dict] | None = None
        self.completions: list = []  # (request, callback result) last run
        self.on_step = None  # default per-loop hook (profiler window)

    # -- warmup ------------------------------------------------------------

    def _warm_llm_plens(self) -> list[int]:
        """One prompt length per compiled prefill width: each armed
        bucket gets the longest admissible prompt that selects it (a
        bucket no admissible prompt can select is skipped — it can never
        compile under traffic either); without buckets, one multi-chunk
        prompt compiles the chunk-loop graph."""
        top = self.cache_len - 2  # warmup decodes 2: plen+2 <= cache_len
        if self.buckets is None:
            C = self.engines[0].prefill_chunk
            return [min(2 * C, top)]
        plens, prev = [], 0
        for b in self.buckets:
            hi = min(b, top)
            if hi > prev:  # a prompt of length hi selects bucket b
                plens.append(hi)
            prev = b
        return plens

    def warmup(self) -> dict:
        """Pre-compile every (bucket, family) graph on every replica
        BEFORE timing starts, then snapshot the jit-cache census that
        ``require_steady_state`` holds ``run()`` to.  Warmup requests
        use negative rids (real traffic uses non-negative) and are
        drained, not reported."""
        from repro.serve.scheduler import Request

        for ei, eng in enumerate(self.engines):
            rid = -(1 + 1000 * ei)  # unique negative ids per replica
            for wi, plen in enumerate(self._warm_llm_plens()):
                # max_new=2 reaches the decode graph (1 would retire at
                # start_decode, before any batched step compiles).  One
                # DISTINCT token per warmup prompt: an earlier warmup
                # registers its prompt pages, and a repeated token would
                # prefix-hit — shrinking the next prompt's real extend and
                # silently skipping the bucket width it was meant to
                # compile.
                tok = 3 + wi % (eng.cfg.vocab - 3)
                eng.submit(Request(rid=rid, prompt=[tok] * plen, max_new=2,
                                   eos=-1))
                rid -= 1
            if (eng.prefill_chunk < eng.page_size
                    and eng.page_size + 2 <= self.cache_len):
                # pre-compile the copy-on-write graph: a full-prefix
                # re-admission of a one-page prompt re-writes the shared
                # tail inside the registered page (chunk-grained restart
                # below the page boundary), which is exactly the CoW the
                # first timed prefix hit would otherwise compile
                dup = [2] * eng.page_size
                for _ in range(2):
                    eng.submit(Request(rid=rid, prompt=dup, max_new=2,
                                       eos=-1))
                    rid -= 1
            if eng.crypto is not None:
                from repro.serve.crypto import CryptoRequest

                ctx = eng.crypto_ctx
                MMp = ctx.baseB.M * ctx.baseBp.M
                n = 5
                while n < ctx.n_max and math.gcd(n, MMp) != 1:
                    n += 2
                eng.submit(CryptoRequest(rid=rid, op="modexp", a=3, b=5,
                                         n=n))
                eng.submit(CryptoRequest(rid=rid - 1, op="modmul", a=2,
                                         b=3, n=n))
                eng.submit(CryptoRequest(rid=rid - 2, op="divmod", a=7,
                                         b=3))
            eng.run_to_completion()
            eng.drain_completed()
            # warmup hits count compile coverage, not traffic: reset
            if eng.prefill_buckets is not None:
                eng.bucket_hits = {b: 0 for b in eng.prefill_buckets}
                eng.bucket_fallbacks = 0
                eng.bucket_pad_tokens = eng.bucket_real_tokens = 0
        self._warm_sizes = [e.jit_cache_sizes() for e in self.engines]
        return {
            "replicas": len(self.engines),
            "warmed_plens": self._warm_llm_plens(),
            "jit_traces": [dict(s) for s in self._warm_sizes],
        }

    # -- steady-state assertion --------------------------------------------

    def require_steady_state(self) -> None:
        """Raise unless the jit-cache census is EXACTLY the warmup
        snapshot — a timed run that compiled anything was mis-warmed and
        its numbers are garbage."""
        if self._warm_sizes is None:
            raise RuntimeError("warmup() has not run")
        live = [e.jit_cache_sizes() for e in self.engines]
        if live != self._warm_sizes:
            raise RuntimeError(
                f"steady state retraced: warmup compiled "
                f"{self._warm_sizes}, after run: {live}"
            )

    def steady_state_ok(self) -> bool:
        try:
            self.require_steady_state()
        except RuntimeError:
            return False
        return True

    # -- timed run ---------------------------------------------------------

    def run(self, reqs: list, *, clock=time.perf_counter,
            on_step=None) -> dict:
        """Replay ``reqs`` under the real clock and report saturation
        metrics.  Arrivals are offsets in seconds from the run's t0
        (offline mode zeroes them: everything available at once);
        ``t_admit/t_first/t_done`` land in the same timebase, so TTFT
        and latency come straight off the request stamps.  ``on_step``
        fires once per driver loop (profiler hook)."""
        if self._warm_sizes is None:
            raise RuntimeError(
                "warmup() must complete before timed traffic — otherwise "
                "the run pays compile time and retraces mid-measurement"
            )
        rs = self.replica_set
        if on_step is None:
            on_step = self.on_step
        reqs = sorted(reqs, key=lambda r: getattr(r, "arrival", 0.0))
        pump = (CompletionPump(self.callback, queue_size=self.queue_size)
                if self.overlap else None)
        inline: list = []
        i, n = 0, len(reqs)
        steps0 = rs.steps
        t0 = clock()
        try:
            while i < n or rs.busy:
                now = clock() - t0
                while i < n and reqs[i].arrival <= now:
                    rs.submit(reqs[i])
                    i += 1
                rs.pump(now)
                if on_step is not None:
                    on_step()
                retired = rs.step_all(clock() - t0)
                for r in retired:
                    if pump is not None:
                        pump.put(r)
                    else:
                        inline.append((r, self.callback(r)))
                if not retired and not rs.stepping and i < n:
                    # idle until the next open-loop arrival (short naps:
                    # an admission may free up before the next arrival)
                    gap = reqs[i].arrival - (clock() - t0)
                    if gap > 0:
                        time.sleep(min(gap, 5e-4))
            if pump is not None:
                pump.flush()  # completion work counts inside the wall
            wall = clock() - t0
        finally:
            if pump is not None:
                pump.close(raise_error=False)
        self.completions = list(pump.completed) if pump is not None \
            else inline
        return self._report(wall, steps0, pump)

    def _report(self, wall: float, steps0: int, pump) -> dict:
        done = [r for r, _ in self.completions]
        llm = [r for r in done if getattr(r, "family", "llm") == "llm"]
        crypto = [r for r in done if getattr(r, "family", "llm")
                  == "crypto"]
        toks = sum(len(r.out) for r in llm)
        report = {
            "requests": len(done),
            "llm_requests": len(llm),
            "crypto_requests": len(crypto),
            "tokens_out": toks,
            "wall_s": wall,
            "arrival_span_s": max(
                (getattr(r, "arrival", 0.0) for r in done), default=0.0
            ),
            "tok_per_s": toks / wall if wall > 0 else 0.0,
            "tok_per_s_per_chip": (toks / wall / self.n_chips)
            if wall > 0 else 0.0,
            "n_chips": self.n_chips,
            "replicas": len(self.engines),
            # device ids holding each replica's weights and KV cache
            "replica_devices": [
                sorted({d.id for leaf in jax.tree_util.tree_leaves(
                    (e.params, e.cache)) for d in leaf.devices()})
                for e in self.engines
            ],
            "engine_steps": self.replica_set.steps - steps0,
            "dispatched": list(self.replica_set.dispatched),
            "ttft_s": sample_stats(
                [r.t_first - r.arrival for r in llm
                 if r.t_first is not None]
            ),
            "latency_s": sample_stats(
                [r.t_done - r.arrival for r in done
                 if r.t_done is not None]
            ),
            "overlap": {
                "enabled": self.overlap,
                **(pump.stats() if pump is not None else {}),
            },
            "retrace_free": self.steady_state_ok(),
            "jit_traces": [dict(e.jit_cache_sizes())
                           for e in self.engines],
        }
        if self.buckets is not None:
            agg = {
                "widths": list(self.buckets),
                "hits": {str(b): 0 for b in self.buckets},
                "fallbacks": 0, "pad_tokens": 0, "real_tokens": 0,
            }
            for e in self.engines:
                st = e.bucket_stats()
                for k, v in st["hits"].items():
                    agg["hits"][k] += v
                for k in ("fallbacks", "pad_tokens", "real_tokens"):
                    agg[k] += st[k]
            agg["pad_overhead"] = (
                agg["pad_tokens"] / agg["real_tokens"]
                if agg["real_tokens"] else 0.0
            )
            report["buckets"] = agg
        report["paging"] = [e.page_stats() for e in self.engines]
        return report
