"""Paged, prefix-sharing KV cache invariants (DESIGN.md §13).

The tier-1 contract of the paged pool under the continuous batcher:

* reference agreement — requests sharing a system-prompt prefix through
  deduplicated pages produce the tokens of a plain batch-1 greedy loop
  over the model (``conftest.reference``) and a full logical KV row (the
  gathered page-table view) within the stated float32 tolerance;
* PAGE savings — N requests sharing a 75%-length common prefix peak at
  STRICTLY fewer physical pages than N full slot rows would hold, under
  the same persistent jitted decode step (no retrace, via jit cache
  stats);
* copy-on-write — a full-prefix admission that must write into a shared
  page copies it first; the source page's readers are untouched;
* eviction — recycling retained pages under pool pressure keeps every
  retired fingerprint valid, drops the evicted page's whole registry
  subtree (a reused pid can never resurrect an orphan chain), and a
  verify MISMATCH at eviction lands in ``verify_log`` under the page's
  publisher rid;
* shared-fingerprint repair — a corrupted shared page codeword is
  detected and repaired ONCE, after which every reader re-verifies;
* validation — capacity errors report derived legal values, not just the
  rejected inputs.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro  # noqa: F401
from conftest import (
    N_PG,
    PAGE,
    check_against_reference,
    logical_rows as _logical_rows,
    make_engine,
    run_with_row_snapshots,
)
from repro.dist.sharding import auto_mesh
from repro.serve.scheduler import (
    FREE,
    PagedScheduler,
    PrefixRegistry,
    Request,
)


_engine = make_engine  # shared factory (tests/conftest.py)


def _prefix_reqs(cfg, n, plen, shared, max_new, seed=3):
    """n requests whose prompts share a ``shared``-token common prefix."""
    rng = np.random.default_rng(seed)
    prefix = [int(t) for t in rng.integers(1, cfg.vocab, shared)]
    return [
        Request(rid=i, prompt=prefix + [int(t) for t in rng.integers(
            1, cfg.vocab, plen - shared)], max_new=max_new)
        for i in range(n)
    ]


# --------------------------------------------------- reference agreement
def test_shared_prefix_bitwise_tokens_and_kv(cfg, params):
    """Three requests behind one system prefix: the reference's tokens,
    and the FULL logical KV (gathered through the page table) within the
    float32 tolerance."""
    reqs = _prefix_reqs(cfg, 3, plen=19, shared=16, max_new=6)
    eng = _engine(cfg, params)
    for r in reqs:
        eng.submit(Request(rid=r.rid, prompt=list(r.prompt),
                           max_new=r.max_new))
    eng.try_admit()
    assert eng.page_stats()["dedup_hits"] > 0  # prefix actually shared
    # snapshot table rows while mapped (release zeroes them at retirement;
    # page CONTENT stays intact because nothing else is admitted after)
    tables = {r.rid: list(eng.sched.table[i]) for i, r in enumerate(reqs)}
    while eng.sched.busy:
        eng.step()
    done = {r.rid: r for r in eng.sched.completed}

    for r in reqs:
        rows = _logical_rows(eng, tables[r.rid])
        # the written region: prompt + all decode writes (the final
        # generated token is never written back)
        end = len(r.prompt) + len(done[r.rid].out) - 1
        check_against_reference(cfg, params, done[r.rid],
                                {n: x[:, :end] for n, x in rows.items()})


def test_full_prefix_hit_cow_bitwise(cfg, params):
    """A prompt that exactly equals already-registered pages must CoW the
    final shared page (first-token logits need a write into it) and still
    give the reference's tokens."""
    rng = np.random.default_rng(11)
    prefix = [int(t) for t in rng.integers(1, cfg.vocab, 16)]
    eng = _engine(cfg, params, prefill_chunk=4)
    eng.submit(Request(rid="warm", prompt=prefix + [5], max_new=3))
    eng.run_to_completion()
    eng.submit(Request(rid="hit", prompt=list(prefix), max_new=4))
    done = eng.run_to_completion()
    assert eng.page_stats()["cow_copies"] >= 1
    hit = [r for r in done if r.rid == "hit"][0]
    check_against_reference(cfg, params, hit)


# ----------------------------------------------------- page-count savings
def test_75pct_shared_prefix_uses_strictly_fewer_pages(cfg, params):
    """8 requests sharing a 75%-length common prefix peak at strictly
    fewer physical pages than 8 full slot rows (8 * n_pg), under ONE
    persistent decode trace."""
    n = 8
    reqs = _prefix_reqs(cfg, n, plen=24, shared=18, max_new=8)
    eng = _engine(cfg, params, n_slots=n, n_pages=1 + n * N_PG)
    for r in reqs:
        eng.submit(r)
    eng.try_admit()
    assert len(eng.sched.decoding_slots()) == n  # all co-resident
    eng.run_to_completion()
    st = eng.page_stats()
    assert st["pages_in_use_peak"] < n * N_PG  # strictly fewer than rows
    assert st["dedup_hits"] >= (n - 1) * (18 // PAGE)
    sizes = eng.jit_cache_sizes()
    assert sizes["decode"] == 1 and sizes["extend"] == 1  # no retrace


def test_admission_defers_on_page_pressure(cfg, params):
    """With a pool smaller than slots * n_pg, admission is gated by PAGES:
    requests defer while reservations can't be covered, then admit as
    retirements free pages — and everything still completes."""
    eng = _engine(cfg, params, n_slots=4, n_pages=N_PG + 2)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=[i * 7 + 1] * 12, max_new=6))
    done = eng.run_to_completion()
    assert len(done) == 4
    assert eng.page_stats()["deferrals"] > 0
    assert eng.page_stats()["pages_in_use"] == 0  # all released


# ------------------------------------------------------------ fingerprints
def test_eviction_and_reuse_keep_fingerprints_valid(cfg, params):
    """Pool pressure evicts retained (registered) pages and recycles them;
    every retirement's per-page verification still passes."""
    eng = _engine(cfg, params, n_slots=2, n_pages=N_PG + 2,
                  rns_verify=True)
    for i in range(4):
        eng.submit(Request(rid=i, prompt=[i * 3 + 2] * 12, max_new=6))
    eng.run_to_completion()
    st = eng.page_stats()
    assert st["pages_evicted"] >= 1
    assert all(eng.verify_log.values())
    assert st["fingerprints"]["failed"] == 0
    assert st["fingerprints"]["verified"] > 0


def test_shared_page_corruption_repaired_once_for_all_readers(cfg, params):
    """Corrupt the ONE stored codeword of a page shared by three readers:
    detected via the redundant channels, repaired in place once, and every
    reader's retirement verification passes against the fixed codeword."""
    rng = np.random.default_rng(7)
    prefix = [int(t) for t in rng.integers(1, cfg.vocab, PAGE)]
    eng = _engine(cfg, params, rns_verify=True)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=prefix + [40 + i], max_new=4))
    eng.try_admit()
    shared = [p for p in range(eng.n_pages)
              if eng.sched.alloc.refcount[p] > 1]
    assert len(shared) == 1  # exactly the one deduplicated prefix page
    pid = shared[0]
    assert pid in eng.wire
    eng.corrupt_wire(pid, channel=1, delta=3)
    assert not eng.wire_ok(pid)  # redundant channels catch it
    assert eng.repair_wire(pid) == {"repaired": 1, "unrecoverable": 0}
    assert eng.wire_ok(pid)
    eng.run_to_completion()
    assert all(eng.verify_log.values())  # every reader re-verified
    assert eng.wire.stats["repaired"] == 1


def test_registry_eviction_cannot_resurrect_orphan_chain():
    """Evicting a registered page drops its ENTIRE descendant subtree:
    children are keyed by the raw parent pid, so if the chain survived and
    the pool reused that pid for different content, match() would walk
    through the reused pid into stale pages whose KV was computed under a
    different prefix (silently wrong tokens)."""
    reg = PrefixRegistry(page_size=2)
    reg.add(None, (1, 2), pid=3)
    reg.add(3, (3, 4), pid=4)
    reg.add(4, (5, 6), pid=5)
    reg.drop(3)  # pid 3 evicted under pool pressure
    assert reg.nodes == {} and reg.by_pid == {}  # whole chain unregistered
    reg.add(None, (9, 9), pid=3)  # pool reuses pid 3 for NEW content
    # the old descendants (pids 4, 5) must not ride behind the reused pid
    assert reg.match([9, 9, 3, 4, 5, 6]) == [3]


def test_eviction_verify_failure_lands_in_verify_log(cfg, params):
    """Corrupt RETAINED pages' stored codewords, then force pool pressure
    to evict them: the eviction-time mismatch is recorded in verify_log
    under the pages' publisher rids, not just counted in wire stats."""
    eng = _engine(cfg, params, n_slots=2, n_pages=N_PG + 2,
                  rns_verify=True)
    eng.submit(Request(rid=0, prompt=[2] * 12, max_new=6))
    eng.submit(Request(rid=1, prompt=[5] * 12, max_new=6))
    eng.run_to_completion()
    assert eng.verify_log == {0: True, 1: True}
    retained = list(eng.sched.alloc.retained)
    assert retained  # registered prefix pages parked for reuse
    pubs = {eng._page_pub[pid] for pid in retained}
    for pid in retained:
        eng.corrupt_wire(pid, channel=1, delta=3)  # stored codeword rots
    for i in (2, 3):  # distinct prompts: no dedup revival, pure pressure
        eng.submit(Request(rid=i, prompt=[i * 3 + 2] * 12, max_new=6))
    eng.run_to_completion()
    assert eng.page_stats()["pages_evicted"] >= 1
    bad = [r for r, ok in eng.verify_log.items() if not ok]
    assert bad and set(bad) <= pubs  # surfaced under the publisher rid(s)
    assert eng.wire.stats["failed"] >= 1


@jax.jit
def _per_page_fp(cache, pid, span):
    """The per-page fingerprint the batched graph replaced: one page's
    per-layer masked K/V sums over its prompt span [0, span)."""
    valid = (jnp.arange(PAGE) < span).astype(jnp.float32)
    sums = []
    for name in ("k", "v"):
        page = jax.lax.dynamic_index_in_dim(cache[name], pid, axis=1,
                                            keepdims=False)
        sums.append(jnp.sum(page.astype(jnp.float32)
                            * valid[None, :, None, None], axis=(1, 2, 3)))
    return jnp.concatenate(sums)


def _oracle_codeword(eng, pid, span):
    """Single-page oracle: ``_per_page_fp``, then an eager encode."""
    fp = _per_page_fp(eng.cache, jnp.int32(pid), jnp.int32(span))
    return np.asarray(eng.codec.encode_array(fp, channel_major=True).residues)


def test_batched_codewords_bitwise_equal_the_per_page_path(cfg, params):
    """Every codeword the batched graph publishes or checks — prompt
    pages with a partial last page, retirement verifies, a multi-page
    eviction list — equals the single-page oracle bitwise, and one
    compiled fingerprint graph serves calls of every page count."""
    eng = _engine(cfg, params, n_slots=2, n_pages=N_PG + 2,
                  rns_verify=True, prefill_buckets=(8, 16, 32))
    calls = []
    batched, barrier = eng._page_codewords, eng._exec_actions
    in_barrier = []

    def spy(pids, spans=None):
        kind = ("evict" if in_barrier
                else "verify" if pids[0] in eng.wire else "publish")
        out = batched(pids, spans)
        if spans is None:
            spans = [eng._page_span[p] for p in pids]
        for pid, span, cw in zip(pids, spans, out):
            np.testing.assert_array_equal(
                cw.residues, _oracle_codeword(eng, pid, span))
        calls.append((kind, len(pids)))
        return out

    def barrier_spy(actions):
        in_barrier.append(1)
        try:
            return barrier(actions)
        finally:
            in_barrier.pop()

    eng._page_codewords, eng._exec_actions = spy, barrier_spy
    rng = np.random.default_rng(5)
    # 27 tokens: N_PG pages, the last holding 3 prompt positions
    eng.submit(Request(rid=0, prompt=[int(t) for t in rng.integers(
        1, cfg.vocab, 27)], max_new=2))
    eng.try_admit()
    pages = [pid for _, pid in eng.sched.slot_pages(0)]
    assert [eng._page_span[p] for p in pages] == [PAGE] * (N_PG - 1) + [3]
    for pid in pages:
        np.testing.assert_array_equal(
            eng.wire.get(pid).residues,
            _oracle_codeword(eng, pid, eng._page_span[pid]))
    eng.run_to_completion()
    # a second distinct 27-token prompt evicts the three retained pages
    for rid, n in ((1, 27), (2, 5), (3, 20)):
        eng.submit(Request(rid=rid, prompt=[int(t) for t in rng.integers(
            1, cfg.vocab, n)], max_new=2))
        eng.run_to_completion()
    published = {n for kind, n in calls if kind == "publish"}
    assert {1, 3, N_PG} <= published
    assert max(n for kind, n in calls if kind == "evict") >= 2
    assert any(kind == "verify" for kind, _ in calls)
    assert all(eng.verify_log.values())
    assert eng.jit_cache_sizes()["fingerprint"] == 1


def test_calls_beyond_n_pg_pages_run_in_chunks(cfg, params):
    """A call over more than n_pg pages runs the one graph once per n_pg
    pages; every codeword still equals the single-page oracle."""
    eng = _engine(cfg, params, n_slots=2, rns_verify=True)
    eng.submit(Request(rid=0, prompt=list(range(1, 26)), max_new=2))
    eng.submit(Request(rid=1, prompt=list(range(40, 61)), max_new=2))
    eng.try_admit()
    pids = sorted(eng._page_span)
    assert len(pids) > N_PG
    fp, n_calls = eng._fp_fn, []
    eng._fp_fn = lambda *a: (n_calls.append(1), fp(*a))[1]
    try:
        fresh = eng._page_codewords(pids * 2)
    finally:
        eng._fp_fn = fp
    assert len(n_calls) == -(-2 * len(pids) // N_PG)
    for pid, cw in zip(pids * 2, fresh):
        np.testing.assert_array_equal(
            cw.residues, _oracle_codeword(eng, pid, eng._page_span[pid]))
    assert eng.jit_cache_sizes()["fingerprint"] == 1


def _corrupt_page(eng, pid):
    """Bump one K value of physical page ``pid`` in the pool."""
    k = eng.cache["k"]
    eng.cache = {**eng.cache, "k": k.at[:, pid, 0].add(
        jnp.asarray(64, k.dtype))}


@pytest.mark.parametrize("where", ["eviction", "retirement"])
def test_one_corrupt_page_fails_only_its_own_reader(cfg, params, where):
    """One page of a multi-page check — the middle of an eviction list,
    or one prompt page of a retiring request — has its K/V bumped: only
    that page's publisher (eviction) or reader (retirement) reads False,
    and the wire stats count exactly one failure."""
    # eviction: 9 usable pages, so the third prompt evicts retained ones;
    # retirement: full backing, so nothing is evicted
    eng = _engine(cfg, params, n_slots=2, cache_len=64,
                  n_pages=10 if where == "eviction" else None,
                  rns_verify=True, prefill_buckets=(8, 16, 32, 64))
    rng = np.random.default_rng(9)

    def prompt(n):
        return [int(t) for t in rng.integers(1, cfg.vocab, n)]

    if where == "eviction":
        hit = {}
        barrier = eng._exec_actions

        def corrupt_middle(actions):
            ev = [a["pid"] for a in actions if a["op"] == "evict"]
            if len(ev) >= 3 and not hit:
                hit["pid"] = ev[len(ev) // 2]
                hit["pub"] = eng._page_pub[hit["pid"]]
                hit["pubs"] = {eng._page_pub[p] for p in ev}
                _corrupt_page(eng, hit["pid"])
            return barrier(actions)

        eng._exec_actions = corrupt_middle
        for rid, n in ((0, 17), (1, 25), (2, 57)):  # 2 + 3 retained pages
            eng.submit(Request(rid=rid, prompt=prompt(n), max_new=2))
            eng.run_to_completion()
        assert hit, "no eviction list of three or more pages"
        assert len(hit["pubs"]) > 1  # the list spans two publishers
        bad, n_reqs = hit["pub"], 3
    else:
        eng.submit(Request(rid=0, prompt=prompt(33), max_new=3))
        eng.submit(Request(rid=1, prompt=prompt(29), max_new=3))
        assert len(eng.try_admit()) == 2  # both resident, no eviction
        slot = next(s for s in eng.sched.slots if s.req and s.req.rid == 0)
        pages = [pid for lp, pid in eng.sched.slot_pages(slot.index)
                 if lp * PAGE < 33]
        assert len(pages) >= 3
        _corrupt_page(eng, pages[1])
        eng.run_to_completion()
        bad, n_reqs = 0, 2
    assert {r for r, ok in eng.verify_log.items() if not ok} == {bad}
    assert len(eng.verify_log) == n_reqs
    assert eng.wire.stats["failed"] == 1


# ---------------------------------------------------------------- sharding
def test_paged_pool_shards_on_mesh(cfg, params):
    """The pooled buffer takes ``cache_specs(paged_pool=True)``'s layout:
    rank-5 leaves with the page-pool axis carrying the batch sharding."""
    mesh = auto_mesh((1,), ("data",))
    eng = _engine(cfg, params, mesh=mesh)
    spec = eng.cache_pspecs["k"]
    assert len(spec) == 5
    eng.submit(Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new=4))
    done = eng.run_to_completion()
    assert len(done[0].out) == 4


# -------------------------------------------------------------- validation
def test_capacity_errors_report_derived_legal_values(cfg, params):
    """Constructor rejections name the legal values, not just the bad
    inputs (page divisors, chunk-compatible sizes, pool minimums)."""
    with pytest.raises(ValueError, match=r"valid page sizes: \[1, 2, 4, "):
        _engine(cfg, params, page_size=5)
    with pytest.raises(ValueError, match="chunk-compatible page sizes"):
        # 32 % 24 and 24 % 32 both nonzero: neither grid contains the other
        _engine(cfg, params, cache_len=96, page_size=32, prefill_chunk=24)
    with pytest.raises(ValueError, match=f"minimum n_pages: {N_PG + 2}"):
        _engine(cfg, params, n_pages=N_PG + 1)
    with pytest.raises(ValueError,
                       match=r"valid prefill_chunk values: \[1, 2, 4, "):
        _engine(cfg, params, prefill_chunk=7)
    with pytest.raises(ValueError, match="nearest legal cache_len: 512 or"):
        _engine(cfg, params, cache_len=513)


# ------------------- padded write barrier (bucketed prefill, DESIGN §13)
def test_bucketed_paged_bitwise_vs_chunk_loop_shared_prefix(cfg, params):
    """THE padded-write-barrier contract: length-bucketed single-call
    prefill on the prefix-sharing pool and the chunk loop both produce
    the reference's tokens and its logical KV rows within the float32
    tolerance.  Pad positions ride the per-slot scratch page — never a
    mapped, shared, or retained physical page — so dedup'd prefixes stay
    intact while every prompt prefills in ONE extend call of its bucket
    width."""
    def mk_reqs():
        shared = _prefix_reqs(cfg, 3, plen=19, shared=16, max_new=6)
        rng = np.random.default_rng(29)
        extras = [Request(rid=10 + i, prompt=[int(t) for t in rng.integers(
            1, cfg.vocab, p)], max_new=6) for i, p in enumerate((5, 11, 23))]
        return shared + extras

    eng_b = _engine(cfg, params, prefill_buckets=(8, 16, 32),
                    rns_verify=True)
    done_b, rows_b = run_with_row_snapshots(eng_b, mk_reqs())
    eng_c = _engine(cfg, params, rns_verify=True)  # the chunk loop
    done_c, rows_c = run_with_row_snapshots(eng_c, mk_reqs())

    assert sorted(done_b) == sorted(done_c)
    for rid, rb in done_b.items():
        assert rb.out == done_c[rid].out
        check_against_reference(cfg, params, rb, rows_b[rid])
        check_against_reference(cfg, params, done_c[rid], rows_c[rid])
    # every retirement's fingerprints verified clean, on BOTH engines
    assert eng_b.verify_log and all(eng_b.verify_log.values())
    assert all(eng_c.verify_log.values())
    st = eng_b.bucket_stats()
    assert sum(st["hits"].values()) == 6 and st["fallbacks"] == 0
    pg = eng_b.page_stats()
    assert pg["dedup_hits"] >= 2 * (16 // PAGE)  # prefix shared via pages
    assert pg["pages_in_use"] == 0  # every span + scratch page released
    assert pg["fingerprints"]["failed"] == 0
    sizes = eng_b.jit_cache_sizes()
    assert sizes["decode"] == 1 and sizes["extend"] == 3  # one per width


def test_bucketed_full_prefix_hit_cow_bitwise(cfg, params):
    """A full-prefix hit restarting mid-page (prefill_chunk < page_size)
    must CoW the final shared page and then extend through a PADDED
    bucket: the pads ride the scratch page, the CoW'd page takes only the
    real tail, and the tokens are still the reference's."""
    rng = np.random.default_rng(11)
    prefix = [int(t) for t in rng.integers(1, cfg.vocab, 16)]
    eng = _engine(cfg, params, prefill_chunk=4,
                  prefill_buckets=(8, 16, 32), rns_verify=True)
    eng.submit(Request(rid="warm", prompt=prefix + [5], max_new=3))
    eng.run_to_completion()
    eng.submit(Request(rid="hit", prompt=list(prefix), max_new=4))
    done = eng.run_to_completion()
    assert eng.page_stats()["cow_copies"] >= 1
    hit = [r for r in done if r.rid == "hit"][0]
    check_against_reference(cfg, params, hit)
    assert all(eng.verify_log.values())
    assert eng.bucket_stats()["hits"]["8"] >= 1  # the padded 4-token tail


def test_bucket_pads_write_only_span_pages_and_scratch(cfg, params):
    """Direct pool-level check of the barrier: a bucketed prefill whose
    bucket overshoots both the prompt AND the table row (pad positions
    clip past cache_len) may touch ONLY the slot's own span page and the
    transient scratch page.  Every other physical page — the retained
    prefix pages it maps, the parking page the clipped pads would
    otherwise junk — is byte-identical before and after."""
    rng = np.random.default_rng(23)
    prefix = [int(t) for t in rng.integers(1, cfg.vocab, 16)]
    eng = _engine(cfg, params, prefill_buckets=(32,), rns_verify=True)
    eng.submit(Request(rid="pub", prompt=prefix + [7, 8, 9], max_new=2))
    eng.run_to_completion()
    before = {n: np.asarray(eng.cache[n]).copy() for n in ("k", "v")}
    reg_pids = set(eng.sched.registry.by_pid)
    assert reg_pids  # the prefix pages are retained, shareable content

    grabbed = []
    orig = eng.sched.alloc_scratch

    def spy(slot):
        pid, acts = orig(slot)
        grabbed.append(pid)
        return pid, acts

    eng.sched.alloc_scratch = spy
    try:
        # 6-token tail behind the shared prefix, forced through the one
        # oversized bucket: 26 pads, positions 32..47 clip past the table
        eng.submit(Request(rid="sub", prompt=prefix + [11] * 6, max_new=2))
        eng.try_admit()  # admission == the single bucketed extend
    finally:
        eng.sched.alloc_scratch = orig
    assert grabbed and len(grabbed) == 1
    scratch = grabbed[0]
    slot = eng.sched.decoding_slots()[0]
    row = list(eng.sched.table[slot.index])
    assert scratch not in row  # never mapped through the table
    assert eng.sched.alloc.refcount[scratch] == 0  # freed after the call
    allowed = {row[2], scratch}  # real span [16, 22) -> logical page 2
    assert reg_pids.isdisjoint(allowed)
    after = {n: np.asarray(eng.cache[n]) for n in ("k", "v")}
    for pid in range(eng.n_pages):
        if pid in allowed:
            continue
        for name in ("k", "v"):
            np.testing.assert_array_equal(after[name][:, pid],
                                          before[name][:, pid])
    eng.run_to_completion()
    assert all(eng.verify_log.values())


def test_bucketed_admission_reserves_scratch_headroom():
    """Host-side reservation math for the bucketed path: real-span pages
    in page units PLUS one scratch unit, consumed exactly by plan_write +
    alloc_scratch; the chunk-loop plan for the same request reserves by
    the chunk-grid pad end instead (no scratch)."""
    s = PagedScheduler(2, 32, page_size=8, n_pages=9, prefill_chunk=8,
                       prefill_buckets=(8, 16, 32))
    assert s.bucket_for(3) == 8 and s.bucket_for(9) == 16
    assert s.bucket_for(33) is None  # over-bucket -> chunk-loop fallback
    s.submit(Request(rid="a", prompt=list(range(10)), max_new=5, eos=-1))
    slot = s.admit_next()
    assert slot is not None
    # ceil((10 + 5 - 1) / 8) = 2 span pages + 1 scratch page
    assert slot.reserved_left == 3
    s.plan_write(slot, 0, 10)  # maps the two span pages
    pid, _ = s.alloc_scratch(slot)
    assert pid not in s.table[slot.index]
    assert s.alloc.refcount[pid] == 1 and not s.alloc.is_retained(pid)
    s.free_scratch(pid)
    assert s.alloc.refcount[pid] == 0 and not s.alloc.is_retained(pid)
    assert slot.reserved_left == 0  # budget exactly spent
    c = PagedScheduler(2, 32, page_size=8, n_pages=9, prefill_chunk=8)
    c.submit(Request(rid="a", prompt=list(range(10)), max_new=5, eos=-1))
    assert c.admit_next().reserved_left == 2  # chunk grid, no scratch


def test_scheduler_deferral_is_pure_host_logic():
    """PagedScheduler admission math without any model: worst-case
    reservation blocks the queue head until pages free up."""
    s = PagedScheduler(4, 32, page_size=8, n_pages=6, prefill_chunk=8)
    s.submit(Request(rid="a", prompt=list(range(24)), max_new=8, eos=-1))
    a = s.admit_next()
    assert a is not None
    for st in range(0, 24, 8):
        s.plan_write(a, st, 8)
    s.submit(Request(rid="b", prompt=list(range(50, 70)), max_new=8,
                     eos=-1))
    assert s.admit_next() is None  # needs 4 pages, only 1 available
    assert s.stats["deferrals"] == 1
    s.release_pages(a.index)
    s.slots[a.index].state = FREE
    s.slots[a.index].req = None
    admitted = s.admit_next()  # pages back -> queue head admits
    assert admitted is not None
    assert admitted.index == a.index  # ...into the actually-released slot
