"""Continuous-batching serve engine over the paged KV pool (DESIGN.md
§12-§13).

One persistent ``jax.jit`` decode step serves every in-flight request at
once.  The KV cache is ONE pooled buffer of fixed-size pages (every leaf
``(L, n_pages, page_size, ...)``); a host-side ``(n_slots, n_pg)`` page
table (``PagedScheduler``) maps each slot's logical pages to physical
ones.  A slot is one row of that table, belonging to at most one request.
New requests are admitted into FREE slots *mid-decode*: the prompt
prefills straight into the pool through the slot's table row (a bucketed
single call, or the fixed-shape chunk loop), and the very next decode
step carries the newcomer along with every already-running stream.
Admission deduplicates shared prompt prefixes: shared pages are
refcounted read-only, and the first write into one triggers a
copy-on-write through a jitted page-copy graph.  The page table and the
per-row positions ride into the decode/extend graphs as DATA (int32 array
arguments, never trace constants), so arrival and departure never change
any traced shape: each graph compiles once per token width, which
``jit_cache_sizes()`` exposes and tests/test_serve_batcher.py asserts.

Slot rows are computationally independent (attention masks per row, MoE
dispatch is per row, every norm/matmul is row-local), so a request's
tokens do not depend on its co-resident traffic; the tier-1 tests hold
them to a plain batch-1 greedy loop over the model (tests/conftest.py).

Pass ``mesh=`` and the pool is placed on ``dist/sharding.cache_specs``'
paged layout over it (the page axis carries the batch sharding).

``rns_verify=True`` arms the RNS integrity path: at admission the engine
fingerprints each new prompt page's immutable span (per-layer sums of
every pool leaf) and encodes it through an RRNS ``GradCodec`` into a
typed channel-major ``RnsArray`` wire buffer, held in a
``dist.fault.WireStore`` keyed by PHYSICAL PAGE, so one codeword covers
every reader of a shared page and is checked when the page is freed or
evicted.  Decode traffic never writes below a slot's prompt length, so at
retirement the recomputed fingerprints must match bitwise — any mismatch
means cross-slot clobbering.  The wire buffers themselves are
locate-and-correct codewords: ``wire_ok`` detects a corrupted stored
buffer via ``verify_packed`` and ``repair_wire`` rebuilds the bad channel
in place with ``dist.fault.repair_packed`` — fault repair composed with
serving (DESIGN.md §12).  Each publish (a prompt's new pages), retirement
verify (the request's prompt pages) and eviction verify (an action list's
evicted pages) is ONE call of the batched ``_fp_pages_impl`` graph —
every page's sums and RRNS encode in one fixed-shape program — and ONE
readback; the page codewords are sliced and compared on the host, and the
wire store holds them as host (NumPy) residues, so a verify runs no
per-page device op.

Profiler spans (``jax.profiler.TraceAnnotation``, free while no trace is
recording) mark each host phase the chip waits on: ``serve.admit``
(``try_admit``), ``serve.step`` (the LLM half of ``step``),
``serve.write_barrier`` (an action list of the page write barrier), and
``serve.fp.publish`` / ``serve.fp.verify`` around fingerprint work, each
with the number of RRNS ``codewords`` it encodes or checks as a stat.

Doctest — admit, stream, retire (a 5-token prompt, 4 greedy tokens)::

    >>> import jax
    >>> from repro.configs import get_config
    >>> from repro.models import init_params
    >>> from repro.serve.batcher import ContinuousBatcher
    >>> from repro.serve.scheduler import Request
    >>> cfg = get_config("gemma-2b").smoke()
    >>> eng = ContinuousBatcher(cfg, init_params(cfg, jax.random.key(0)),
    ...                         n_slots=2, cache_len=32, prefill_chunk=8)
    >>> eng.submit(Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new=4))
    >>> done = eng.run_to_completion()
    >>> [(r.rid, len(r.out)) for r in done]
    [(0, 4)]
    >>> eng.jit_cache_sizes()["decode"]         # one persistent trace
    1
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.dist.sharding import cache_specs, named_shardings
from repro.models import decode_step, extend_step
from repro.models.moe import dispatch_form
from repro.serve.scheduler import PagedScheduler, Request, Slot
from repro.serve.serve_step import paged_pool_abstract

__all__ = ["ContinuousBatcher"]

_SUPPORTED = ("dense", "moe")


def _fp_leaves(cache) -> list[str]:
    """The cache leaves a fingerprint reduces, in a fixed order: every
    per-layer stack (``k``/``v``, or MLA's ``c_kv``/``k_pe``), not the
    ``len`` scalar."""
    return sorted(n for n, x in cache.items() if getattr(x, "ndim", 0) >= 3)


def _masked_layer_sums(x, valid):
    """(L,) f32 sums of ``x`` (L, S, ...) over positions where ``valid``
    (S,) is 1, and every trailing axis."""
    tail = (None,) * (x.ndim - 2)
    return jnp.sum(x.astype(jnp.float32) * valid[(None, slice(None)) + tail],
                   axis=tuple(range(1, x.ndim)))


def _zero_cache(abs_tree):
    """Concrete all-zero state matching an abstract pytree (the pool's
    "len" becomes the int32 scalar 0)."""
    return jax.tree_util.tree_map(
        lambda l: jnp.zeros(l.shape, l.dtype), abs_tree
    )


class ContinuousBatcher:
    """Slot-based continuous batching over the paged KV pool.

    Parameters
    ----------
    cfg, params : the model (linear-KV transformer families: dense/moe).
        Sliding-window archs are lowered to the masked full-length cache
        layout (``window_cache=False``) so every slot row is linear.
    n_slots : rows of the page table = max concurrent requests.
    cache_len : per-slot KV capacity; every request needs
        ``len(prompt) + max_new <= cache_len``.
    prefill_chunk : token-chunk size of the admission prefill loop — long
        prompts run as ceil(plen/chunk) calls of ONE fixed-shape graph.
    prefill_buckets : optional ascending tuple of prompt-length buckets.
        Admission pads the prompt to the smallest bucket >= plen and
        runs ONE extend call per prompt instead of the chunk loop — one
        compiled graph per bucket width, pre-compiled by the offline
        harness's warmup.  Prompts longer than the largest bucket fall
        back to the chunked loop (counted in ``bucket_stats()``).
        The bucket is chosen by the tokens LEFT to compute after the
        shared-prefix skip; real tokens write through the ordinary
        page-table barrier, and pad tokens scatter into a per-call
        scratch page that is freed immediately — the padded write
        barrier of DESIGN.md §13.
    rns_verify : arm the RnsArray cache-integrity fingerprints.
    mesh : optional ``jax.sharding.Mesh``; the pool is placed on
        ``dist.sharding.cache_specs``' paged layout over it.
    page_size : tokens per page of the pool (must divide ``cache_len``
        and align with ``prefill_chunk``).
    n_pages : physical pages in the pool.  Defaults to
        ``1 + n_slots * (cache_len // page_size)`` — parking page plus
        full backing for every slot, i.e. zero admission deferrals; a
        smaller pool oversubscribes slots against pages.
    crypto_slots : slots of the big-integer crypto lane (DESIGN.md §15);
        0 (default) disables the second request family entirely.  With
        crypto armed, ``submit`` dispatches on the request's ``family``
        tag: ``serve.crypto.CryptoRequest`` rides the crypto lane,
        ``Request`` the LLM lane, and both share the tick clock, the
        verify log, and (under ``rns_verify``) the wire store.
    crypto_ctx : optional ``serve.crypto.CryptoContext``; defaults to a
        fresh context (8 limbs per base, 32-bit exponents).
    crypto_chunk : Montgomery-ladder bits advanced per engine tick; must
        divide the context's ``exp_bits``.
    """

    def __init__(self, cfg, params, *, n_slots: int, cache_len: int,
                 prefill_chunk: int = 32,
                 prefill_buckets: tuple | None = None,
                 rns_verify: bool = False,
                 mesh=None, page_size: int = 16,
                 n_pages: int | None = None,
                 crypto_slots: int = 0, crypto_ctx=None,
                 crypto_chunk: int = 8):
        cfg.validate()
        if cfg.family not in _SUPPORTED:
            raise NotImplementedError(
                f"continuous batching needs a linear-KV transformer family "
                f"{_SUPPORTED}, not {cfg.family!r} (SSM/hybrid state and "
                f"encoder caches do not page yet)"
            )
        if cfg.kv_quant:
            raise NotImplementedError(
                "int8 KV slots need per-slot scale re-estimation at "
                "admission; run the batcher on the fp cache layout"
            )
        if cfg.window and cfg.window_cache:
            # grouped ring caches can't take per-row positions; the masked
            # full-length layout is semantically identical (more HBM)
            cfg = dataclasses.replace(cfg, window_cache=False)
        if cache_len > 512 and cache_len % 512:
            lo, hi = cache_len // 512 * 512, -(-cache_len // 512) * 512
            raise ValueError(
                f"cache_len={cache_len} beyond one flash chunk must be a "
                f"multiple of 512 (prefill eval_shape runs the chunked "
                f"attention); nearest legal cache_len: {lo} or {hi}"
            )
        divisors = [d for d in range(1, cache_len + 1) if cache_len % d == 0]
        if cache_len % prefill_chunk:
            # a prompt padded to the chunk grid could otherwise run past
            # the row and XLA's update-slice clamp would silently shift
            # the write window backwards over earlier positions
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must divide "
                f"cache_len={cache_len}; valid prefill_chunk values: "
                f"{divisors}"
            )
        self.cfg, self.params = cfg, params
        self.prefill_chunk = C = int(prefill_chunk)
        self.rns_verify = bool(rns_verify)
        self.page_size = ps = int(page_size)
        # extend and decode launches of a MoE model by the expert dispatch
        # form their token width selects (``models.moe.dispatch_form``)
        self.moe_launches = {"grouped": 0, "buffer": 0}

        self.prefill_buckets: tuple[int, ...] | None = None
        if prefill_buckets is not None:
            bks = tuple(sorted({int(b) for b in prefill_buckets}))
            if not bks:
                raise ValueError("prefill_buckets must name >= 1 bucket")
            for b in bks:
                if b < 1 or b > cache_len:
                    raise ValueError(
                        f"bucket {b} out of range 1..cache_len={cache_len}"
                    )
                if b > 512 and b % 512:
                    raise ValueError(
                        f"bucket {b} beyond one flash chunk must be a "
                        f"multiple of 512 (the padded extend runs the "
                        f"chunked attention)"
                    )
            self.prefill_buckets = bks
            # admission-time accounting the offline harness reports:
            # hits per bucket width, chunk-loop fallbacks, pad waste
            self.bucket_hits: dict[int, int] = {b: 0 for b in bks}
            self.bucket_fallbacks = 0
            self.bucket_pad_tokens = 0
            self.bucket_real_tokens = 0

        if cache_len % ps:
            raise ValueError(
                f"page_size={ps} must divide cache_len={cache_len}; "
                f"valid page sizes: {divisors}"
            )
        if ps % C and C % ps:
            # page-aligned OR chunk-aligned prefill writes; anything
            # else makes every chunk straddle page ownership checks
            legal = [d for d in divisors if d % C == 0 or C % d == 0]
            raise ValueError(
                f"page_size={ps} must align with prefill_chunk={C} "
                f"(one must divide the other); chunk-compatible page "
                f"sizes for cache_len={cache_len}: {legal}"
            )
        if ps > 512 and ps % 512:
            raise ValueError(
                f"page_size={ps} beyond one flash chunk must be a "
                f"multiple of 512 (the pool abstract runs the chunked "
                f"prefill per page); nearest legal page_size: "
                f"{ps // 512 * 512} or {-(-ps // 512) * 512}"
            )
        n_pg = cache_len // ps
        if n_pages is None:
            n_pages = 1 + n_slots * n_pg
        min_pages = n_pg + 2
        if n_pages < min_pages:
            raise ValueError(
                f"n_pages={n_pages} cannot guarantee admission of one "
                f"max-length request: cache_len={cache_len} / "
                f"page_size={ps} = {n_pg} logical pages, plus the "
                f"parking page and one page of mid-page-divergence "
                f"headroom; minimum n_pages: {min_pages}"
            )
        self.n_pages = int(n_pages)
        self.sched = PagedScheduler(
            n_slots, cache_len, page_size=ps, n_pages=self.n_pages,
            prefill_chunk=C, prefill_buckets=self.prefill_buckets,
        )

        params_abs = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), params
        )
        pool_abs = paged_pool_abstract(cfg, params_abs, self.n_pages, ps)
        self.cache = _zero_cache(pool_abs)
        self.mesh = mesh
        cache_out = rep_out = None  # jit out_shardings: unpinned
        if mesh is not None:
            self.cache_pspecs = cache_specs(pool_abs, mesh, paged_pool=True)
            cache_sh = named_shardings(self.cache_pspecs, mesh)
            self.cache = jax.device_put(self.cache, cache_sh)
            # the engine owns its mesh: weights and the other device state
            # live there too, or a replica's steps would run against the
            # default device's copies
            rep_out = named_shardings(jax.sharding.PartitionSpec(), mesh)
            self.params = jax.device_put(params, rep_out)
            # every graph hands the cache back in the layout it was given:
            # an output spec the partitioner rewrote (P() for P(None, ...))
            # would key a second trace of the same width mid-run
            cache_out = cache_sh
        step_out = None if mesh is None else (rep_out, cache_out)

        # The engine's jitted graphs — each traces exactly once per
        # process (per token width for the extend) because every argument
        # keeps a fixed shape across admissions, retirements, and
        # arbitrary slot occupancy: the page table is an int32 ARRAY
        # argument, its contents data, never trace constants.
        # valid/scratch are traced int32 DATA too (the padded write
        # barrier): the chunk loop passes valid = chunk width (all tokens
        # through the table, chunk-grid pads included) with the parking
        # page as a dead scratch operand; bucketed prefill passes valid =
        # real tokens + a live scratch page.
        self._extend_fn = jax.jit(self._extend_paged_impl,
                                  out_shardings=step_out)
        self._decode_fn = jax.jit(self._decode_paged_impl,
                                  out_shardings=step_out)
        self._copy_fn = jax.jit(self._copy_impl, out_shardings=cache_out)
        self._fp_fn = jax.jit(self._fp_pages_impl) if rns_verify else None
        if rns_verify:
            from repro.dist.fault import WireStore
            from repro.dist.grad_codec import GradCodec

            # world=1: fingerprints are fresh encodings, wraps=0 repairs
            self.codec = GradCodec.make(world=1, correct=True)
            # keyed by physical page (and ("crypto", rid) for lane slots)
            self.wire = WireStore(self.codec)
            self._page_span: dict[int, int] = {}
            # physical page -> rid whose prefill published its codeword,
            # so corruption detected at EVICTION (no retiring request in
            # hand) still lands in verify_log under a request id
            self._page_pub: dict[int, object] = {}
            self.verify_log: dict[int, bool] = {}

        # Crypto lane (DESIGN.md §15): a second request family on the same
        # engine.  Its jitted graphs follow the exact no-retrace contract
        # of the LLM graphs above — fixed shapes, slot ids and cursors as
        # data — and its per-slot fingerprints share the LLM wire store
        # under ("crypto", rid) keys.
        self.crypto = None
        if crypto_slots:
            from repro.serve.crypto import (
                CryptoContext, CryptoLane, make_crypto_fns,
            )
            from repro.serve.serve_step import crypto_state_abstract

            self.crypto_ctx = (
                crypto_ctx if crypto_ctx is not None else CryptoContext()
            )
            self.crypto = CryptoLane(
                int(crypto_slots), self.crypto_ctx.exp_bits,
                int(crypto_chunk),
            )
            self.crypto_state = _zero_cache(
                crypto_state_abstract(self.crypto_ctx, int(crypto_slots))
            )
            if mesh is not None:
                self.crypto_state = jax.device_put(self.crypto_state,
                                                   rep_out)
            self._crypto_fns = make_crypto_fns(
                self.crypto_ctx, int(crypto_chunk)
            )
        elif crypto_ctx is not None:
            raise ValueError("crypto_ctx= given but crypto_slots=0; pass "
                             "crypto_slots>=1 to enable the crypto lane")

    def _first_token(self, logits) -> int:
        """Greedy first token from the prefill's last-position logits (a
        host sync)."""
        return int(jnp.argmax(logits[0, 0]))

    @property
    def _wire(self) -> dict:
        """Raw key -> RnsArray mapping of the wire store (physical page
        ids, and ``("crypto", rid)`` for crypto lane slots)."""
        return self.wire.raw

    # ------------------------------------------------------ jitted graphs
    def _extend_paged_impl(self, params, cache, tokens, pos, idx, pages,
                           valid, scratch):
        """One prefill chunk (or padded bucket) of one slot, the logits of
        position ``idx`` only: the first ``valid`` tokens write into the
        pool through the (1, n_pg) page-table row, the rest into page
        ``scratch`` (the padded write barrier)."""
        return extend_step(self.cfg, params, cache, tokens, pos,
                           logit_index=idx, pages=pages,
                           page_size=self.page_size, valid_len=valid,
                           scratch=scratch)

    def _decode_paged_impl(self, params, cache, tokens, pos, pages):
        """One batched decode step + greedy sampling.  tokens (n_slots, 1),
        pos (n_slots,) per-slot write positions; the (n_slots, n_pg) page
        table routes each row's read gather and token write
        (models/attention.py ``attn_decode_paged``)."""
        logits, cache = decode_step(
            self.cfg, params, cache, tokens, pos,
            pages=pages, page_size=self.page_size,
        )
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    def _copy_impl(self, cache, src, dst):
        """Copy physical page ``src`` over page ``dst`` on every pool leaf
        — the device half of copy-on-write (traced page ids: one graph
        serves every copy)."""
        def one(leaf):
            if getattr(leaf, "ndim", 0) < 2:
                return leaf
            page = jax.lax.dynamic_index_in_dim(
                leaf, src, axis=1, keepdims=True
            )
            return jax.lax.dynamic_update_slice_in_dim(
                leaf, page, dst, axis=1
            )

        return jax.tree_util.tree_map(one, cache)

    def _fp_pages_impl(self, cache, pids, spans):
        """Per-layer masked sums of every pool leaf (``_fp_leaves``) over
        each listed physical page's prompt span [0, spans[i]),
        RRNS-encoded in the same graph -> (n_channels, n_pg * W) int32
        residues, W = n_leaves * L, page i's codeword in columns
        [W*i, W*(i+1)).  ``pids``/``spans`` are fixed (n_pg,)
        int32 vectors with the live entries first and span 0 past them,
        so one graph serves every call; a loop of one turn per live
        entry reduces one page at a time, never gathering more."""
        ps, n_pg = self.page_size, pids.shape[0]
        names = _fp_leaves(cache)

        def one(i, sums):
            valid = (jnp.arange(ps) < spans[i]).astype(jnp.float32)
            row = []
            for name in names:
                page = jax.lax.dynamic_index_in_dim(
                    cache[name], pids[i], axis=1, keepdims=False
                )  # (L, page, ...)
                row.append(_masked_layer_sums(page, valid))
            return sums.at[i].set(jnp.concatenate(row))

        width = self._fp_width()
        sums = jax.lax.fori_loop(
            0, jnp.sum(spans > 0, dtype=jnp.int32), one,
            jnp.zeros((n_pg, width), jnp.float32),
        )
        return self.codec.encode_packed(sums.reshape(-1), channel_major=True)

    # ---------------------------------------------------- pool host glue
    def _fp_width(self) -> int:
        """Codeword width of one page: a sum per layer of each leaf."""
        names = _fp_leaves(self.cache)
        return len(names) * self.cache[names[0]].shape[0]

    def _page_codewords(self, pids: list, spans: list | None = None) -> list:
        """Freshly recomputed RRNS codewords of physical pages ``pids``
        over their prompt spans (``spans``, else the stored ones): one
        call of the batched graph and one readback per ``n_pg`` pages,
        each codeword a host-held channel-major ``RnsArray``."""
        if spans is None:
            spans = [self._page_span[pid] for pid in pids]
        n_pg = self.sched.n_pg
        width = self._fp_width()
        out = []
        for c0 in range(0, len(pids), n_pg):
            n = len(pids[c0:c0 + n_pg])
            ids, span = np.zeros((2, n_pg), np.int32)
            ids[:n], span[:n] = pids[c0:c0 + n], spans[c0:c0 + n]
            res = np.asarray(self._fp_fn(self.cache, ids, span))
            out += [self.wire.host_array(res[:, i * width:(i + 1) * width])
                    for i in range(n)]
        return out

    def _exec_actions(self, actions: list) -> None:
        """Execute a ``PagedScheduler.plan_write`` action list: evictions
        verify-and-drop the page's fingerprint, CoW runs the jitted page
        copy, fresh allocs need no device work.  The evictions go first:
        no copy in the list writes a page that the list evicts after it
        (a copy's target is a page the list already took, evicting it
        first if need be), so every evicted page is verified with its
        content intact.  An eviction-verify MISMATCH is cache corruption
        caught at the last possible moment — it is recorded in
        ``verify_log`` under the page's publisher rid (and in the wire
        stats), not just counted."""
        if not actions:
            return
        with TraceAnnotation("serve.write_barrier"):
            evicted = [a["pid"] for a in actions if a["op"] == "evict"
                       and self.rns_verify and a["pid"] in self.wire]
            if evicted:
                with TraceAnnotation("serve.fp.verify",
                                     codewords=len(evicted)):
                    fresh = self._page_codewords(evicted)
                    for pid, cw in zip(evicted, fresh):
                        ok = self.wire.matches(pid, cw)
                        pub = self._page_pub.pop(pid, None)
                        if not ok:
                            self.verify_log[pub] = False
                        self.wire.pop(pid)
                        self._page_span.pop(pid, None)
            for act in actions:
                if act["op"] == "cow":
                    self.cache = self._copy_fn(
                        self.cache, jnp.int32(act["src"]),
                        jnp.int32(act["dst"])
                    )

    # ------------------------------------------------------ admission path
    def _rid_held(self, rid) -> bool:
        """Is ``rid``'s verify state still live in EITHER family?  The
        verify log is one rid-keyed dict shared across families, so a
        collision in either lane corrupts attribution for both."""
        held = (
            rid in self.verify_log
            or any(q.rid == rid for q in self.sched.queue)
            or any(s.req is not None and s.req.rid == rid
                   for s in self.sched.slots)
        )
        if self.crypto is not None:
            held = held or (
                any(q.rid == rid for q in self.crypto.queue)
                or any(s.req is not None and s.req.rid == rid
                       for s in self.crypto.slots)
                or ("crypto", rid) in self.wire
            )
        return held

    def submit(self, req) -> None:
        """Queue one request; dispatches on ``req.family`` ("llm" default
        / "crypto" when the crypto lane is armed)."""
        family = getattr(req, "family", "llm")
        if family == "crypto":
            if self.crypto is None:
                raise ValueError(
                    "engine built without crypto_slots=; pass "
                    "crypto_slots>=1 to accept crypto-family requests"
                )
            self.crypto_ctx.validate(req)
        elif family != "llm":
            raise ValueError(f"unknown request family {family!r}; "
                             f"expected 'llm' or 'crypto'")
        if self.rns_verify and self._rid_held(req.rid):
            # verify state is keyed on rid; refuse the collision
            # before any slot is bound or device work runs
            raise ValueError(
                f"rid {req.rid} already holds verify state (queued, in "
                f"flight, or retired-undrained); use unique rids, or "
                f"drain_completed() between reuses"
            )
        if family == "crypto":
            self.crypto.queue.append(req)
        else:
            self.sched.submit(req)

    def try_admit(self, now: float = 0.0) -> list[Slot]:
        """Admit as many queued requests as there are FREE slots; each
        admission prefills the prompt into the pool through the slot's
        page-table row.  Returns the admitted slots (normally now in
        DECODE; already FREE again if the first token retired the
        request — one-token budget or instant EOS)."""
        with TraceAnnotation("serve.admit"):
            admitted = []
            while True:
                slot = self.sched.admit_next(now)
                if slot is None:
                    break
                self._prefill_into(slot, now)
                admitted.append(slot)
            if self.crypto is not None:
                self._crypto_admit(now)
        return admitted

    def _prefill_into(self, slot: Slot, now: float) -> None:
        """Admission prefill: chunks write straight into the pool through
        the slot's page-table row.  Positions below
        ``slot.prefill_start`` are NOT recomputed — the scheduler mapped
        registry pages holding that shared prefix at admission; each
        chunk's write barrier (``plan_write``) allocates/CoWs the pages
        the chunk lands on before its extend runs.

        With a bucket ladder, a prompt whose remaining extend fits a
        bucket prefills in ONE padded call through the padded write
        barrier (DESIGN.md §13): the real span goes through the normal
        page-table barrier, while every pad token scatters into a
        one-call scratch page taken from the slot's reservation — pad
        K/V never lands in a shared, registered, or retained page, so
        dedup/CoW/fingerprints see exactly the rows the chunk loop
        would have written."""
        req = slot.req
        prompt = [int(t) for t in req.prompt]
        plen, C = len(prompt), self.prefill_chunk
        start = slot.prefill_start
        need = plen - start  # tokens the extend actually computes
        bucket = self.sched.bucket_for(need)
        if bucket is not None:
            self._exec_actions(self.sched.plan_write(slot, start, need))
            scratch, acts = self.sched.alloc_scratch(slot)
            self._exec_actions(acts)
            pages_row = jnp.asarray(
                [self.sched.table[slot.index]], jnp.int32
            )
            toks = jnp.asarray(
                [prompt[start:] + [0] * (bucket - need)], jnp.int32
            )
            self._count_moe(bucket)
            logits, self.cache = self._extend_fn(
                self.params, self.cache, toks, jnp.int32(start),
                jnp.int32(need - 1), pages_row, jnp.int32(need),
                jnp.int32(scratch),
            )
            self.sched.free_scratch(scratch)
            self.bucket_hits[bucket] += 1
            self.bucket_pad_tokens += bucket - need
            self.bucket_real_tokens += need
        else:
            n_chunks = -(-need // C)
            if self.prefill_buckets is not None:
                self.bucket_fallbacks += 1
                self.bucket_pad_tokens += n_chunks * C - need
                self.bucket_real_tokens += need
            padded = prompt + [0] * (start + n_chunks * C - plen)
            last = (plen - 1) - (start + (n_chunks - 1) * C)
            for ci in range(n_chunks):
                s0 = start + ci * C
                self._exec_actions(self.sched.plan_write(slot, s0, C))
                pages_row = jnp.asarray(
                    [self.sched.table[slot.index]], jnp.int32
                )
                toks = jnp.asarray([padded[s0:s0 + C]], jnp.int32)
                idx = last if ci == n_chunks - 1 else 0
                # chunk-grid pads keep writing THROUGH the table (their
                # pages are reserved for this slot's decode span anyway):
                # valid = full width, parking page as dead scratch operand
                self._count_moe(C)
                logits, self.cache = self._extend_fn(
                    self.params, self.cache, toks, jnp.int32(s0),
                    jnp.int32(idx), pages_row, jnp.int32(C), jnp.int32(0),
                )
        first = self._first_token(logits)
        # publish fully-covered prompt pages for later admissions to share
        self.sched.register_prompt(slot, prompt)
        if self.rns_verify:
            self._fingerprint_prompt_pages(slot, plen)
        if self.sched.start_decode(slot, first, now):
            self._retire(req)

    def _fingerprint_prompt_pages(self, slot: Slot, plen: int) -> None:
        """Encode one RRNS codeword per prompt page of ``slot`` that does
        not already carry one — shared registry pages keep their original
        publisher's codeword (that sharing is the point: one wire entry
        covers every reader)."""
        ps = self.page_size
        new = []
        for lp, pid in self.sched.slot_pages(slot.index):
            off = lp * ps
            if off >= plen:
                break  # decode-region pages are mutable: never fingerprinted
            if pid not in self.wire:
                new.append((pid, min(ps, plen - off)))
        if not new:
            return
        pids, spans = [pid for pid, _ in new], [span for _, span in new]
        with TraceAnnotation("serve.fp.publish", codewords=len(new)):
            fresh = self._page_codewords(pids, spans)
            for pid, span, cw in zip(pids, spans, fresh):
                self._page_span[pid] = span
                self._page_pub[pid] = slot.req.rid
                self.wire.put(pid, cw)

    def _retire(self, req: Request) -> None:
        """Retirement: verify the request's prompt-page fingerprints
        while its table row is still mapped, then release the row —
        ``'freed'`` pages drop their codewords (already verified),
        ``'retained'``/``'shared'`` pages keep them for future/current
        readers."""
        if self.rns_verify:
            self.verify_log[req.rid] = self.verify_request(req)
        for pid, disp in self.sched.release_pages(req.slot_index):
            if disp == "freed" and self.rns_verify:
                self.wire.pop(pid)
                self._page_span.pop(pid, None)
                self._page_pub.pop(pid, None)

    # --------------------------------------------------------- crypto lane
    def _crypto_row(self, v):
        return jnp.asarray(np.asarray(v))[None, :]

    def _crypto_admit(self, now: float) -> None:
        """Drain the crypto queue: one-shots (modmul/divmod) execute and
        retire inside this call; modexp binds a FREE lane slot and writes
        its ladder state (publishing the slot fingerprint when
        ``rns_verify`` is armed).  Stops when a modexp finds no free slot
        — FIFO order is preserved within the family."""
        lane, ctx = self.crypto, self.crypto_ctx
        while lane.queue:
            req = lane.queue[0]
            if req.op == "modexp":
                slot = lane.free_slot()
                if slot is None:
                    return
                lane.queue.popleft()
                self._crypto_bind(slot, req, now)
            else:
                lane.queue.popleft()
                req.t_admit = now
                req.result = (self._crypto_divmod(req)
                              if req.op == "divmod"
                              else self._crypto_modmul(req))
                req.t_done = now
                lane.completed.append(req)
                if self.rns_verify:
                    # one-shots hold no resident device state to corrupt;
                    # log them verified so rid accounting stays uniform
                    self.verify_log[req.rid] = True

    def _crypto_bind(self, slot, req, now: float) -> None:
        ctx, row = self.crypto_ctx, self._crypto_row
        from repro.serve.crypto import encode_exponent

        c = ctx.consts_for(req.n)
        a = req.a % req.n
        self.crypto_state = self._crypto_fns["admit"](
            self.crypto_state, jnp.int32(slot.index),
            row(ctx.encode_lo(a)), row(ctx.encode_hi(a)),
            row(c["m2_lo"]), row(c["m2_hi"]),
            row(c["one_lo"]), row(c["one_hi"]),
            row(c["neg"]), row(c["n_lo"]), row(c["n_hi"]),
            row(encode_exponent(ctx, req.b)),
        )
        self.crypto.bind(slot, req, now)
        if self.rns_verify:
            with TraceAnnotation("serve.fp.publish", codewords=1):
                fp = self._crypto_fns["fp"](
                    self.crypto_state, jnp.int32(slot.index)
                )
                self.wire.put(("crypto", req.rid), self.codec.encode_array(
                    fp, channel_major=True
                ))

    def _crypto_modmul(self, req) -> int:
        ctx, row = self.crypto_ctx, self._crypto_row
        c = ctx.consts_for(req.n)
        a, b = req.a % req.n, req.b % req.n
        out = self._crypto_fns["modmul"](
            row(ctx.encode_lo(a)), row(ctx.encode_hi(a)),
            row(ctx.encode_lo(b)), row(ctx.encode_hi(b)),
            row(c["m2_lo"]), row(c["m2_hi"]),
            row(c["neg"]), row(c["n_hi"]), row(c["n_lo"]),
        )
        return ctx.decode_lo(np.asarray(out)[0])

    def _crypto_divmod(self, req) -> tuple:
        ctx, row = self.crypto_ctx, self._crypto_row
        # Alg.-1 packed layout: base channels + m_a (RRNS contexts just
        # drop their extra m_b channel here — divmod runs on (n+1) rows)
        xp = row(ctx.encode_lo(req.a)[: ctx.n + 1])
        dp = row(ctx.encode_lo(req.b)[: ctx.n + 1])
        q, r = self._crypto_fns["divmod"](xp, dp)
        return (ctx.decode_lo(np.asarray(q)[0]),
                ctx.decode_lo(np.asarray(r)[0]))

    def _crypto_step(self, now: float) -> list:
        """Advance every RUN lane slot ``crypto_chunk`` ladder bits and
        retire the slots whose cursor reaches ``exp_bits``."""
        lane = self.crypto
        running = lane.running_slots()
        if not running:
            return []
        cursors = jnp.asarray([s.cursor for s in lane.slots], jnp.int32)
        active = jnp.asarray(
            [1 if s.state == "RUN" else 0 for s in lane.slots], jnp.int32
        )
        self.crypto_state = self._crypto_fns["step"](
            self.crypto_state, cursors, active
        )
        retired = []
        for slot in running:
            slot.cursor += lane.chunk
            if slot.cursor >= lane.exp_bits:
                retired.append(self._crypto_retire(slot, now))
        return retired

    def _crypto_retire(self, slot, now: float):
        """Exit the Montgomery domain, decode the canonical result to a
        Python int, and verify the slot fingerprint against the wire
        codeword published at admission."""
        req = slot.req
        out = self._crypto_fns["final"](
            self.crypto_state, jnp.int32(slot.index)
        )
        req.result = self.crypto_ctx.decode_lo(np.asarray(out)[0])
        if self.rns_verify:
            self.verify_log[req.rid] = self.verify_request(req)
        return self.crypto.retire(slot, now)

    # --------------------------------------------------------- decode loop
    def step(self, now: float = 0.0) -> list[Request]:
        """One persistent batched decode step over every DECODE slot,
        plus one ``crypto_chunk``-bit ladder advance of the crypto lane
        when it is armed; returns the requests (both families) that
        retired this step.  The LLM half runs under the ``serve.step``
        profiler span."""
        crypto_retired = (
            self._crypto_step(now) if self.crypto is not None else []
        )
        decoding = self.sched.decoding_slots()
        if not decoding:
            return crypto_retired
        with TraceAnnotation("serve.step"):
            # write barrier for this step's one-token writes: page-
            # boundary crossings allocate, divergence into a shared page
            # CoWs — all BEFORE the table snapshot rides into the decode
            # graph
            for slot in decoding:
                self._exec_actions(
                    self.sched.plan_write(slot, slot.next_pos, 1)
                )
            toks, poss = self.sched.step_rows()
            step_args = [
                self.params,
                self.cache,
                jnp.asarray(toks, jnp.int32)[:, None],
                jnp.asarray(poss, jnp.int32),
                jnp.asarray(self.sched.table, jnp.int32),
            ]
            self._count_moe(1)
            nxt, self.cache = self._decode_fn(*step_args)
            nxt = np.asarray(nxt)
            retired = []
            for slot in decoding:
                self.sched.advance(slot)
                req = slot.req
                if self.sched.record_token(slot, int(nxt[slot.index]),
                                           now):
                    retired.append(req)
                    self._retire(req)
        return retired + crypto_retired

    @property
    def busy(self) -> bool:
        """Work anywhere in the engine: LLM queue/slots or crypto lane."""
        return self.sched.busy or (
            self.crypto is not None and self.crypto.busy
        )

    def run_to_completion(self, max_steps: int = 1 << 20) -> list[Request]:
        """Drain queue and slots (all arrivals already submitted)."""
        steps = 0
        while self.busy:
            self.try_admit(float(steps))
            if self.sched.decoding_slots() or (
                self.crypto is not None and self.crypto.running_slots()
            ):
                self.step(float(steps))
            steps += 1
            if steps > max_steps:
                raise RuntimeError("serve loop exceeded max_steps")
        if self.crypto is None:
            return self.sched.completed
        return list(self.sched.completed) + list(self.crypto.completed)

    def drain_completed(self) -> list[Request]:
        """Hand back the retired requests and release the engine-held
        state keyed on them (wire buffers, verify entries).  A long-lived
        server calls this after reading each batch of results — without
        it, retired-request state (host Request objects, verify entries
        and crypto slot codewords) accumulates for the engine's
        lifetime.  LLM wire buffers are page-keyed and already released
        with their pages at retirement."""
        done, self.sched.completed = self.sched.completed, []
        if self.crypto is not None:
            done = done + self.crypto.completed
            self.crypto.completed = []
        if self.rns_verify:
            for r in done:
                if getattr(r, "family", "llm") == "crypto":
                    self.wire.pop(("crypto", r.rid), None)
                self.verify_log.pop(r.rid, None)
        return done

    def jit_cache_sizes(self) -> dict:
        """Compiled-graph counts per engine function — the no-retrace
        invariant says every value stays at most 1 for the engine's
        lifetime (``copy`` compiles at the first copy-on-write; with
        ``prefill_buckets`` armed, ``extend`` instead stays at the
        number of distinct padded widths the warmup compiled: the graph
        keys on token shape, and every width is pre-compiled before
        timed traffic)."""
        sizes = {
            "decode": self._decode_fn._cache_size(),
            "extend": self._extend_fn._cache_size(),
            "copy": self._copy_fn._cache_size(),
        }
        if self._fp_fn is not None:
            sizes["fingerprint"] = self._fp_fn._cache_size()
        if self.crypto is not None:
            for name in ("admit", "step", "final", "modmul", "divmod"):
                sizes[f"crypto_{name}"] = (
                    self._crypto_fns[name]._cache_size()
                )
            if self.rns_verify:
                sizes["crypto_fingerprint"] = (
                    self._crypto_fns["fp"]._cache_size()
                )
        return sizes

    def _count_moe(self, width: int) -> None:
        if self.cfg.family == "moe":
            self.moe_launches[dispatch_form(self.cfg, width)] += 1

    def moe_stats(self) -> dict:
        """Extend and decode launches of a MoE model by expert dispatch
        form, ``{"grouped": n, "buffer": n}``: how often the grouped form
        (models/moe.py) runs in place of the capacity buffer."""
        return dict(self.moe_launches)

    def bucket_stats(self) -> dict:
        """Bucketed-prefill accounting: hits per width, chunk-loop
        fallbacks, and pad overhead (pad tokens / real tokens) — the
        ``buckets`` block of the offline harness report.  Fallback
        prompts count too (their chunk-grid pads and real tokens), so
        ``pad_overhead`` covers ALL prefill traffic, not only the
        bucketed slice.  "Real" means the tokens the extend computed — a
        shared prefix mapped from the registry is neither padded nor
        recomputed, so it appears in neither term."""
        if self.prefill_buckets is None:
            raise RuntimeError("engine built without prefill_buckets=")
        real = self.bucket_real_tokens
        return {
            "widths": list(self.prefill_buckets),
            "hits": {str(b): n for b, n in self.bucket_hits.items()},
            "fallbacks": self.bucket_fallbacks,
            "pad_tokens": self.bucket_pad_tokens,
            "real_tokens": real,
            "pad_overhead": (self.bucket_pad_tokens / real) if real else 0.0,
        }

    def page_stats(self) -> dict:
        """Pool / dedup / CoW counters, plus the per-page fingerprint
        verify/repair counters when ``rns_verify`` is armed — the
        ``paging`` block of ``launch/serve.py --report``."""
        stats = self.sched.page_stats()
        if self.rns_verify:
            stats["fingerprints"] = dict(self.wire.stats)
        return stats

    # ---------------------------------------------------- warm restart
    def _params_sha(self) -> str:
        import hashlib

        from repro.dist.fault import tree_fingerprints

        fps = tree_fingerprints(self.params)
        joined = "".join(f"{k}={v};" for k, v in sorted(fps.items()))
        return hashlib.sha256(joined.encode()).hexdigest()[:16]

    def _retained_chain(self) -> list[int]:
        """Registered retained pages with live codewords, parents before
        children (restore must adopt in this order)."""
        reg, al = self.sched.registry, self.sched.alloc
        out, queue = [], list(reg.children.get(None, ()))
        while queue:
            pid = queue.pop(0)
            if al.is_retained(pid) and pid in self.wire:
                out.append(pid)
                queue.extend(reg.children.get(pid, ()))
        return out

    def save_warm_state(self, state_dir: str) -> dict:
        """Persist the pool for a warm restart (DESIGN.md §14): the
        pooled cache leaves, every retained page's RRNS codeword, and the
        registry chain metadata, written through the RRNS checkpoint
        format (train/checkpointer.write_step_dir) so the saved state is
        itself single-channel self-healing.  Engine must be idle."""
        self._require_verify()
        if self.sched.busy:
            raise RuntimeError("cannot snapshot warm state mid-flight: "
                               "drain the engine first")
        from repro.train import checkpointer as ckpt

        reg = self.sched.registry
        chain = self._retained_chain()
        pages = []
        for pid in chain:
            parent_key, toks = reg.by_pid[pid]
            pages.append({
                "pid": pid,
                "parent": parent_key,
                "toks": [int(t) for t in toks],
                "span": int(self._page_span[pid]),
                "pub": self._page_pub.get(pid),
            })
        tree = {"cache": self.cache}
        if chain:
            tree["wire"] = {str(pid): np.asarray(self.wire.get(pid).residues)
                            for pid in chain}
        extra = {
            "geometry": {"page_size": self.page_size,
                         "n_pages": self.n_pages},
            "params_sha": self._params_sha(),
            "pages": pages,
        }
        ckpt.write_step_dir(state_dir, 0, tree, extra=extra)
        return {"pages_saved": len(pages)}

    def load_warm_state(self, state_dir: str) -> dict:
        """Rehydrate a ``save_warm_state`` snapshot into a FRESH engine:
        restore the pool cache, then revalidate every persisted page —
        codeword self-check (``ok``), RRNS repair on failure, and a
        recomputed-fingerprint match against the restored cache content —
        adopting survivors as retained registry chains and DROPPING
        failures (with their descendants, since children chain through
        the parent's pid).  A restarted server thus re-verifies shared
        prefix pages instead of discarding them.

        Returns the revalidation report; raises FileNotFoundError when
        nothing restorable exists under ``state_dir``."""
        self._require_verify()
        if (self.sched.busy or self.sched.alloc.in_use
                or self.sched.alloc.retained or self.sched.registry.by_pid):
            raise RuntimeError("warm state must load into a fresh engine")
        from repro.train import checkpointer as ckpt

        tree, _, extra, ck_rep = ckpt.restore(state_dir)
        geo = extra["geometry"]
        if (geo["page_size"] != self.page_size
                or geo["n_pages"] != self.n_pages):
            raise ValueError(
                f"warm state geometry {geo} does not match engine "
                f"(page_size={self.page_size}, n_pages={self.n_pages})")
        if extra["params_sha"] != self._params_sha():
            raise ValueError(
                "warm state was saved under different params — its KV "
                "content would be wrong for this model")
        from repro.train.checkpoint import _flatten

        names, leaves, treedef = _flatten(self.cache)
        got, got_leaves, _ = _flatten(tree["cache"])
        if names != got:
            raise ValueError(f"cache tree mismatch: {set(names) ^ set(got)}")
        for n, mine, theirs in zip(names, leaves, got_leaves):
            if mine.shape != theirs.shape or mine.dtype != theirs.dtype:
                raise ValueError(
                    f"cache leaf {n!r}: saved {theirs.shape}/{theirs.dtype}"
                    f" vs engine {mine.shape}/{mine.dtype}")
        cache = jax.tree_util.tree_unflatten(treedef, got_leaves)
        if self.mesh is not None:
            cache = jax.device_put(
                cache, named_shardings(self.cache_pspecs, self.mesh))
        else:
            cache = jax.tree_util.tree_map(jnp.asarray, cache)
        self.cache = cache

        wire_raw = tree.get("wire", {})
        report = {"pages_saved": len(extra["pages"]), "adopted": 0,
                  "repaired_pages": 0, "dropped": 0,
                  "ckpt_repaired_leaves": ck_rep["repaired_leaves"]}
        # the restored content is fixed from here on: every persisted
        # page's fresh codeword comes from one batched call up front
        fresh = self._page_codewords([int(e["pid"]) for e in extra["pages"]],
                                     [int(e["span"]) for e in extra["pages"]])
        for entry, cw in zip(extra["pages"], fresh):
            pid, parent = int(entry["pid"]), entry["parent"]
            if parent is not None:
                parent = int(parent)
                if parent not in self.sched.registry.by_pid:
                    report["dropped"] += 1  # parent fell: subtree dies
                    continue
            raw = wire_raw.get(str(pid))
            if raw is None:
                report["dropped"] += 1
                continue
            self.wire.put(pid, self.wire.host_array(raw))
            self._page_span[pid] = int(entry["span"])
            repaired_here = False
            if not self.wire.ok(pid):
                rep = self.wire.repair(pid)
                repaired_here = rep["repaired"] > 0
                if rep["unrecoverable"] or not self.wire.ok(pid):
                    self.wire.pop(pid)
                    self._page_span.pop(pid, None)
                    report["dropped"] += 1
                    continue
            if not self.wire.matches(pid, cw):
                # content/fingerprint disagree: the page is not trustworthy
                self.wire.pop(pid)
                self._page_span.pop(pid, None)
                report["dropped"] += 1
                continue
            self.sched.adopt_page(pid, parent, tuple(entry["toks"]))
            if entry.get("pub") is not None:
                self._page_pub[pid] = entry["pub"]
            report["adopted"] += 1
            report["repaired_pages"] += int(repaired_here)
        return report

    # ------------------------------------------------- RNS integrity path
    def _require_verify(self):
        if not self.rns_verify:
            raise RuntimeError("engine built without rns_verify=True")

    def verify_request(self, req: Request) -> bool:
        """Recompute ``req``'s prompt-region fingerprints and compare
        their RNS encodings bitwise against the stored wire buffers.

        One codeword per mapped prompt PAGE of the slot's table row
        (shared pages check against the original publisher's codeword —
        the dedup dataflow of DESIGN.md §13).  Valid until the row is
        reused by a later admission; the engine calls this automatically
        at retirement.

        Crypto-family requests verify their lane slot's immutable device
        rows (exponent bits + modulus channel constants) against the
        ``("crypto", rid)`` codeword published at admission."""
        self._require_verify()
        if getattr(req, "family", "llm") == "crypto":
            with TraceAnnotation("serve.fp.verify", codewords=1):
                fp = self._crypto_fns["fp"](
                    self.crypto_state, jnp.int32(req.slot_index)
                )
                fresh = self.codec.encode_array(fp, channel_major=True)
                return self.wire.matches(("crypto", req.rid), fresh)
        pids = []
        for lp, pid in self.sched.slot_pages(req.slot_index):
            if lp * self.page_size >= len(req.prompt):
                break  # decode-region pages carry no fingerprints
            if pid in self.wire:
                pids.append(pid)
        ok = True
        with TraceAnnotation("serve.fp.verify", codewords=len(pids)):
            for pid, cw in zip(pids, self._page_codewords(pids)):
                ok &= self.wire.matches(pid, cw)
        return ok

    def wire_ok(self, key) -> bool:
        """Codeword self-consistency of one stored wire buffer (RRNS
        redundant-channel check) — detects corruption of the stored
        fingerprint itself, without touching the cache.  ``key`` is a
        physical page id, or ``("crypto", rid)`` for a crypto lane slot."""
        self._require_verify()
        return self.wire.ok(key)

    def repair_wire(self, key) -> dict:
        """Locate-and-correct one stored wire buffer in place via
        ``dist.fault.repair_packed``; returns its report dict.  A shared
        page's buffer is repaired ONCE and every reader re-verifies
        against the fixed codeword."""
        self._require_verify()
        return self.wire.repair(key)

    def corrupt_wire(self, key, channel: int = 0, delta: int = 1,
                     index: int = 0) -> None:
        """Fault injection for tests/drivers: modular-bump one residue of
        a stored wire buffer (stays a syntactically valid residue so the
        corruption is only catchable by the redundant channels)."""
        self._require_verify()
        self.wire.corrupt(key, channel=channel, delta=delta, index=index)
