"""The LLM cells: one ``ContinuousBatcher`` on the paged pool behind the
program's ``ReplicaSet`` admission queue, driven open-loop for a fixed
window.

The loop is ``serve/offline.py``'s ``OfflineInference.run`` cut to a
fixed window: it submits every request when it is due, lets the replica
set dispatch, admits (each admission prefills and yields a first token),
runs one batched decode step, and stamps on the host clock every token
that appeared since the last look.  Times are in seconds from the
window's start; a request's times are stamped after the engine call that
produced them returns, which is when this program hands a token back.

Traffic starts ``preroll_s`` seconds (from the mix file) before the
window opens, so that the window sees the pool, the slots and the queue
in their steady state rather than filling from empty; nothing before the
window is counted.

For the comparison that decides ``correct``, the engine keeps the pool
rows of some requests that retire in the window: the rows each wrote
into every leaf of the paged pool, prompt and served tokens alike, read
through its page-table row at the moment it retires (before the pages
are released), under each leaf's own name in the pool.  It keeps the
longest request so far, and the requests a draw from the seed picks;
the reference recomputes those rows after the window.
"""
from __future__ import annotations

import time
from contextlib import nullcontext

import gen_traffic
import model_config


def _gather_pages(leaves: dict, pages):
    """The pages ``pages`` of every leaf, in one program."""
    return {name: x[:, pages] for name, x in leaves.items()}


class Engine:
    """Weights, engine and replica set of one LLM configuration."""

    def __init__(self, spec: dict, seed: int):
        import jax
        from repro.launch.serve import serving_params
        from repro.serve.batcher import ContinuousBatcher
        from repro.serve.offline import ReplicaSet

        eng = spec["engine"]
        self.spec, self.cfg = spec, model_config.model_config(spec)
        self.buckets = model_config.buckets(eng)
        self.params = serving_params(self.cfg, seed)
        self.eng = ContinuousBatcher(
            self.cfg, self.params, n_slots=eng["n_slots"],
            cache_len=eng["cache_len"], prefill_chunk=eng["prefill_chunk"],
            prefill_buckets=self.buckets, rns_verify=eng["rns_verify"],
            page_size=eng["page_size"], n_pages=eng["n_pages"] or None)
        self.rs = ReplicaSet([self.eng])
        self.verify_ok = self.verify_failed = 0
        self.warm_sizes = None
        # pool rows kept at retirement: rid -> {leaf name: (L, rows, ...)}
        self.kv_rows: dict = {}
        self.keep = None                 # keep(req) -> bool, set per window
        self._gather = jax.jit(_gather_pages)
        self._record = self.eng.sched.record_token
        self.eng.sched.record_token = self._record_and_keep

    def page_bucket(self, rows: int) -> int:
        """Pages gathered for ``rows`` rows: the next power of two, so
        that a few gather programs serve every length."""
        ps, n_pg = self.eng.page_size, self.eng.sched.n_pg
        need = max(1, -(-rows // ps))
        return min(n_pg, 1 << (need - 1).bit_length())

    def _record_and_keep(self, slot, token, now=0.0) -> bool:
        req, idx = slot.req, slot.index
        done = self._record(slot, token, now)
        if done and self.keep is not None and self.keep(req):
            self.kv_rows[req.rid] = self.rows_of(
                self.eng.sched.table[idx], len(req.prompt) + len(req.out) - 1)
        return done

    def paged_leaves(self) -> dict:
        """Every leaf of the engine's pool with the paged layout
        ``(L, n_pages, page_size, ...)``, under its own name (its key
        path in the pool, ``/``-joined)."""
        import jax

        geo = (self.eng.n_pages, self.eng.page_size)
        return {jax.tree_util.keystr(path, simple=True, separator="/"): x
                for path, x in
                jax.tree_util.tree_leaves_with_path(self.eng.cache)
                if x.ndim >= 3 and tuple(x.shape[1:3]) == geo}

    def rows_of(self, table_row, rows: int) -> dict:
        """Rows [0, rows) of every layer of every paged leaf, read out of
        the pool through one slot's page-table row: ``{name: (L, rows,
        ...)}``."""
        import jax.numpy as jnp
        import numpy as np

        b = self.page_bucket(rows)
        got = self._gather(self.paged_leaves(),
                           jnp.asarray(list(table_row)[:b], jnp.int32))
        out = {}
        for name, x in got.items():
            x = np.asarray(x)            # (L, b, page, ...)
            out[name] = x.reshape(x.shape[0], -1, *x.shape[3:])[:, :rows]
        return out

    def warm_llm(self) -> None:
        """Compile and run every bucket width and the decode step once.
        Each warm-up prompt repeats one token of its own, so that no two
        share a prefix and each lands in the bucket it is sized for."""
        from repro.serve.scheduler import Request

        top = self.spec["engine"]["cache_len"] - 2
        for i, b in enumerate(self.buckets):
            tok = self.cfg.vocab - 1 - i
            self.eng.submit(Request(rid=-1 - i, prompt=[tok] * min(b, top),
                                    max_new=2, eos=-1))
            self.eng.run_to_completion()
        self.drain()
        # the pool gathers of every page count a kept request can need
        row = [0] * self.eng.sched.n_pg
        b = 1
        while True:
            self.rows_of(row, b * self.eng.page_size)
            if b >= self.eng.sched.n_pg:
                break
            b = min(2 * b, self.eng.sched.n_pg)

    def jit_sizes(self) -> dict:
        """Compiled programs per engine function, and the pool gather's."""
        return {**self.eng.jit_cache_sizes(),
                "bench_gather": self._gather._cache_size()}

    def snapshot(self) -> None:
        self.warm_sizes = self.jit_sizes()

    def retraces(self) -> int:
        now = self.jit_sizes()
        return sum(now[k] - self.warm_sizes.get(k, 0) for k in now)

    def drain(self) -> list:
        if self.eng.rns_verify:
            for ok in self.eng.verify_log.values():
                self.verify_ok += bool(ok)
                self.verify_failed += not ok
        return self.eng.drain_completed()

    def counters(self) -> dict:
        st = self.eng.page_stats()
        bk = self.eng.bucket_stats()
        return {"dedup_hits": st["dedup_hits"], "cow_copies": st["cow_copies"],
                "deferrals": st["deferrals"], "evicted": st["pages_evicted"],
                "real_tokens": bk["real_tokens"], "pad_tokens": bk["pad_tokens"],
                "page_size": st["page_size"]}

    def free(self) -> None:
        """Drop every device buffer the engine holds."""
        import gc

        import jax

        self.eng = self.rs = self.params = self._gather = None
        gc.collect()
        jax.clear_caches()


class Window:
    """Records of one measured window: requests, engine steps, spans.
    Times are seconds from the window's start."""

    def __init__(self):
        self.reqs: list[dict] = []       # every request submitted
        self.steps: list[dict] = []      # decode steps in the window
        self.admits: list[dict] = []     # admissions in the window
        self.lateness: list[float] = []  # submit - due, due in the window
        self.t_close = None
        self.open_clock = None           # host clock when the window opened
        self.admit_s = 0.0               # seconds admitting, in the window
        self.trace_span = None           # (start, stop) of the trace
        self.counters = {}


def _stamp(live: list, t: float) -> None:
    for rec in live:
        n = len(rec["req"].out)
        while len(rec["times"]) < n:
            rec["times"].append(t)


SAMPLE_SHARE = 0.25   # share of retiring requests whose rows a draw keeps


def run_window(E: Engine, traffic: dict, seed: int, seconds: float, *,
               trace_dir: str | None = None, trace_s: float = 20.0,
               clock=time.perf_counter) -> Window:
    """Drive the open loop through the mix's pre-roll and then for
    ``seconds``; with ``trace_dir`` the profiler records the window's last
    ``trace_s`` seconds.  Requests that retire in the window keep their
    pool rows (``E.kv_rows``) when they are the longest so far or a draw
    from the seed picks them."""
    import jax
    from repro.serve.scheduler import Request

    pre = float(traffic.get("preroll_s", 0.0))
    W = Window()
    pending = gen_traffic.open_loop(traffic, seed, E.cfg.vocab, pre + seconds)
    pending.sort(key=lambda r: r["due"])
    for item in pending:
        item["due"] -= pre
    picked = gen_traffic.rng_for(seed, "sample").random(len(pending)) \
        < SAMPLE_SHARE
    longest = {"rid": None, "n": -1}
    live: list[dict] = []
    eng, rs = E.eng, E.rs
    span = (lambda name: jax.profiler.TraceAnnotation(name)) \
        if trace_dir else (lambda name: nullcontext())
    tr0 = max(0.0, seconds - trace_s)
    tracing = opened = False

    def keep(req) -> bool:
        if not opened:
            return False
        n = len(req.prompt) + len(req.out)
        if n > longest["n"]:
            old = longest["rid"]
            if old is not None and not picked[old]:
                E.kv_rows.pop(old, None)
            longest.update(rid=req.rid, n=n)
            return True
        return bool(picked[req.rid])

    E.kv_rows, E.keep = {}, keep
    t0 = clock() + pre
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        if not opened and now >= 0:
            opened, W.open_clock = True, t0
            W.counters = E.counters()
        if trace_dir and not tracing and now >= tr0:
            jax.profiler.start_trace(trace_dir)
            tracing, tstart = True, clock() - t0
        with span("bench.submit"):
            while pending and pending[0]["due"] <= now:
                item = pending.pop(0)
                if item["due"] >= 0:
                    W.lateness.append(now - item["due"])
                rec = {"rid": item["rid"], "due": item["due"],
                       "plen": len(item["prompt"]),
                       "max_new": item["max_new"], "times": [],
                       "req": Request(rid=item["rid"], prompt=item["prompt"],
                                      max_new=item["max_new"], eos=-1)}
                W.reqs.append(rec)
                live.append(rec)
                rs.submit(rec["req"])
            rs.pump(now)
        t_a = clock() - t0
        with span("bench.admit"):
            admitted = eng.try_admit(now)
        t_adm = clock() - t0
        _stamp(live, t_adm)
        if opened and admitted:
            W.admit_s += t_adm - t_a
            W.admits += [{"t": t_adm, "start": s.prefill_start,
                          "plen": len(s.req.prompt)}
                         for s in admitted if s.req is not None]
        decoding = eng.sched.decoding_slots()
        if decoding:
            ctx = [s.next_pos + 1 for s in decoding]
            with span("bench.decode"):
                eng.step(now)
            t_end = clock() - t0
            _stamp(live, t_end)
            if opened:
                W.steps.append({"t0": t_adm, "t1": t_end, "ctx": ctx})
        done = E.drain()
        if done:
            t_done = clock() - t0
            fin = {id(r) for r in done}
            for rec in live:
                if id(rec["req"]) in fin:
                    rec["done"] = t_done
            live = [rec for rec in live if "done" not in rec]
        if not decoding and not admitted and pending:
            gap = pending[0]["due"] - (clock() - t0)
            if gap > 0:
                with span("bench.idle"):
                    time.sleep(min(gap, 5e-4))
    W.t_close = clock() - t0
    E.keep = None
    if tracing:
        W.trace_span = (tstart, W.t_close)
        jax.profiler.stop_trace()
    end = E.counters()
    W.counters = {k: end[k] - W.counters[k] if k != "page_size" else end[k]
                  for k in end}
    return W


def sample_finished(W: Window, E: Engine, seed: int, min_tokens: int = 256,
                    max_reqs: int = 8) -> list[dict]:
    """The requests the reference checks, each with the pool rows it wrote
    under the leaves' names: the longest request that retired in the
    window, then the ones the seed's draw kept, in an order drawn from the
    seed, until ``min_tokens`` served tokens or ``max_reqs`` requests."""
    fin = sorted((r for r in W.reqs if r["rid"] in E.kv_rows),
                 key=lambda r: r["rid"])
    if not fin:
        return []
    longest = max(fin, key=lambda r: (r["plen"] + len(r["req"].out),
                                      -r["rid"]))
    order = gen_traffic.rng_for(seed, "sample order").permutation(len(fin))
    out, toks = [longest], len(longest["req"].out)
    for i in order:
        if toks >= min_tokens or len(out) >= max_reqs:
            break
        if fin[i] is not longest:
            out.append(fin[i])
            toks += len(fin[i]["req"].out)
    return [{"rid": r["rid"], "prompt": list(r["req"].prompt),
             "out": list(r["req"].out), **E.kv_rows[r["rid"]]} for r in out]
