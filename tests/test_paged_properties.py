"""Property-based paged-serve state machines (hypothesis.stateful).

Two machines drive the HOST side of the paged pool — no model, no device
arrays — through random operation interleavings:

* ``PageAllocatorMachine`` mirrors ``PageAllocator`` against an exact
  shadow model (free-list order, refcounts, LRU retention order), so
  alloc/ref/revive/deref/adopt sequences must reproduce the model's
  predictions bit-for-bit — including WHICH page an eviction recycles;
* ``PagedServeMachine`` interleaves submit / admit / chunked + bucketed
  prefill (with the scratch-page dance of the padded write barrier) /
  decode / early-EOS retirement / warm-restart adoption on a pool small
  enough to force deferrals and evictions, checking global invariants
  after every step: page conservation (free / retained / referenced
  partition the pool), refcounts equal table mappings, the table stays
  one ``(n_slots, n_pg)`` int32 array that hands out Python-int page
  ids, registered pages are never free (no resurrected pid), and every
  registered page with no readers is parked in the retained LRU.

Requires the optional ``hypothesis`` dev dependency (requirements-dev
.txt); skips cleanly when absent.  The CI ``soak`` job raises the
example budget via ``HYPOTHESIS_PROFILE=soak``.
"""
import os
from collections import Counter

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed — pip install -r requirements-dev.txt",
)

from hypothesis import HealthCheck, settings, strategies as st
from hypothesis.stateful import RuleBasedStateMachine, invariant, rule

import repro  # noqa: F401
from repro.serve.scheduler import (
    DECODE,
    PREFILL,
    PageAllocator,
    PagedScheduler,
    Request,
)

settings.register_profile(
    "default", max_examples=20, stateful_step_count=30, deadline=None,
    suppress_health_check=[HealthCheck.too_slow],
)
settings.register_profile(
    "soak", max_examples=150, stateful_step_count=100, deadline=None,
    suppress_health_check=[HealthCheck.too_slow],
)
settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "default"))


class PageAllocatorMachine(RuleBasedStateMachine):
    """Exact-mirror model of the allocator: every transition's return
    value AND the full (free order, refcounts, LRU order) state must
    match the shadow model, so LRU-retention misorderings or free-list
    corruption fail on the precise step that introduced them."""

    N = 8

    def __init__(self):
        super().__init__()
        self.al = PageAllocator(self.N)
        self.free = list(range(self.N - 1, 0, -1))  # pop() -> lowest pid
        self.rc = {p: 0 for p in range(1, self.N)}
        self.lru: list[int] = []  # retained, LRU first

    @rule()
    def alloc(self):
        if not (self.free or self.lru):
            return  # pool dry: reservation gating forbids alloc here
        pid, evicted = self.al.alloc()
        if self.free:
            assert (pid, evicted) == (self.free.pop(), False)
        else:
            assert (pid, evicted) == (self.lru.pop(0), True)  # LRU victim
        self.rc[pid] = 1

    @rule(data=st.data())
    def ref(self, data):
        live = [p for p, c in self.rc.items() if c > 0]
        if not live:
            return
        p = data.draw(st.sampled_from(live))
        self.al.ref(p)
        self.rc[p] += 1

    @rule(data=st.data())
    def revive(self, data):
        if not self.lru:
            return
        p = data.draw(st.sampled_from(self.lru))
        self.al.ref(p)  # retained -> active: leaves the evictable set
        self.lru.remove(p)
        self.rc[p] = 1

    @rule(data=st.data(), retain=st.booleans())
    def deref(self, data, retain):
        live = [p for p, c in self.rc.items() if c > 0]
        if not live:
            return
        p = data.draw(st.sampled_from(live))
        disp = self.al.deref(p, retain=retain)
        self.rc[p] -= 1
        if self.rc[p] > 0:
            assert disp == "shared"
        elif retain:
            assert disp == "retained"
            self.lru.append(p)  # parks at the MRU end
        else:
            assert disp == "freed"
            self.free.append(p)  # LIFO reuse

    @rule(data=st.data())
    def adopt(self, data):
        if not self.free:
            return
        p = data.draw(st.sampled_from(self.free))
        self.al.adopt_retained(p)
        self.free.remove(p)
        self.lru.append(p)

    @invariant()
    def mirrors_model(self):
        assert list(self.al.free) == self.free
        assert list(self.al.retained) == self.lru
        assert [self.al.refcount[p] for p in range(1, self.N)] == [
            self.rc[p] for p in range(1, self.N)]
        assert self.al.in_use == sum(1 for c in self.rc.values() if c > 0)
        assert self.al.available == len(self.free) + len(self.lru)


class PagedServeMachine(RuleBasedStateMachine):
    """Random interleavings over a live ``PagedScheduler``: 3 slots over
    an 8-usable-page pool (worst-case single request needs 5 units), so
    admissions defer, retained prefixes get evicted, and bucketed
    prefills race chunked ones across slots."""

    CACHE_LEN, PAGE, CHUNK = 32, 8, 8
    BUCKETS = (8, 16, 32)

    def __init__(self):
        super().__init__()
        self.s = PagedScheduler(
            3, self.CACHE_LEN, page_size=self.PAGE, n_pages=9,
            prefill_chunk=self.CHUNK, prefill_buckets=self.BUCKETS,
        )
        self.rid = 0
        self.adopt_tok = 1000  # unique tokens: adopted chains never collide
        self.prefill_pos: dict[int, int] = {}  # slot index -> next start
        self.retired: set = set()

    @rule(data=st.data())
    def submit(self, data):
        if len(self.s.queue) >= 4:
            return  # bounded backlog keeps runs converging
        plen = data.draw(st.integers(1, 24))
        max_new = data.draw(st.integers(1, min(6, self.CACHE_LEN - plen)))
        prompt = data.draw(
            st.lists(st.integers(1, 3), min_size=plen, max_size=plen))
        eos = data.draw(st.sampled_from([-1, 2]))  # early-EOS coverage
        self.s.submit(Request(rid=self.rid, prompt=prompt, max_new=max_new,
                              eos=eos))
        self.rid += 1

    @rule()
    def admit(self):
        slot = self.s.admit_next()
        if slot is not None:
            self.prefill_pos[slot.index] = slot.prefill_start

    @rule(data=st.data(), bucketed=st.booleans())
    def prefill(self, data, bucketed):
        slots = [sl for sl in self.s.slots if sl.state == PREFILL]
        if not slots:
            return
        slot = data.draw(st.sampled_from(slots))
        prompt = [int(t) for t in slot.req.prompt]
        plen = len(prompt)
        start = self.prefill_pos[slot.index]
        need = plen - start
        if bucketed and self.s.bucket_for(need) is not None:
            # the padded-bucket path: one barrier over the whole tail,
            # pads absorbed by a transient scratch page
            self.s.plan_write(slot, start, need)
            pid, _ = self.s.alloc_scratch(slot)
            assert pid not in self.s.table[slot.index]
            self.s.free_scratch(pid)
            start = plen
        else:
            # the chunk loop writes its chunk-grid pads THROUGH the table
            self.s.plan_write(slot, start, self.CHUNK)
            start += self.CHUNK
        self.prefill_pos[slot.index] = start
        if start >= plen:
            self.s.register_prompt(slot, prompt)
            first = data.draw(st.integers(1, 3))
            idx = slot.index
            if self.s.start_decode(slot, first):
                self._retire(idx)

    @rule(data=st.data())
    def decode(self, data):
        slots = self.s.decoding_slots()
        if not slots:
            return
        slot = data.draw(st.sampled_from(slots))
        self.s.plan_write(slot, slot.next_pos, 1)
        self.s.advance(slot)
        idx = slot.index
        if self.s.record_token(slot, data.draw(st.integers(1, 3))):
            self._retire(idx)

    @rule(data=st.data(), depth=st.integers(1, 2))
    def warm_adopt(self, data, depth):
        """Restore-time seeding: free pages become retained registry
        chains, parents first — exactly the state release left them in a
        previous process."""
        parent = None
        for _ in range(depth):
            if not self.s.alloc.free:
                return
            pid = data.draw(st.sampled_from(list(self.s.alloc.free)))
            toks = tuple(range(self.adopt_tok, self.adopt_tok + self.PAGE))
            self.adopt_tok += self.PAGE
            self.s.adopt_page(pid, parent, toks)
            parent = pid

    def _retire(self, slot_index):
        req = self.s.completed[-1]
        assert req.rid not in self.retired  # no resurrected request
        self.retired.add(req.rid)
        released = self.s.release_pages(slot_index)
        assert all(type(pid) is int for pid, _ in released)

    @invariant()
    def pool_is_conserved(self):
        al = self.s.alloc
        free, retained = set(al.free), set(al.retained)
        assert free.isdisjoint(retained)
        for p in range(1, al.n_pages):
            rc = al.refcount[p]
            assert rc >= 0
            if p in free or p in retained:
                assert rc == 0
            elif rc == 0:
                pytest.fail(f"page {p} orphaned: rc 0, not free/retained")
        assert al.in_use == sum(
            1 for p in range(1, al.n_pages) if al.refcount[p] > 0)

    @invariant()
    def refcounts_equal_table_mappings(self):
        # no scratch page is live between rules, so every reference is a
        # table mapping (shared pages count once per reader row)
        cnt = Counter(pid for row in self.s.table for pid in row if pid)
        for p in range(1, self.s.alloc.n_pages):
            assert self.s.alloc.refcount[p] == cnt.get(p, 0), f"page {p}"

    @invariant()
    def table_is_one_int32_array(self):
        t = self.s.table
        assert isinstance(t, np.ndarray) and t.dtype == np.int32
        assert t.shape == (3, self.CACHE_LEN // self.PAGE)
        for slot in self.s.slots:
            pages = self.s.slot_pages(slot.index)
            assert all(type(lp) is int and type(p) is int
                       for lp, p in pages)

    @invariant()
    def registry_and_retention_agree(self):
        al, reg = self.s.alloc, self.s.registry
        registered = set(reg.by_pid)
        assert registered.isdisjoint(al.free)  # no resurrected pid
        # a registered page with no readers is always parked retained;
        # the converse is deliberately false — subtree-dropped
        # descendants of an evicted parent linger retained (unreachable,
        # evictable) until the pool recycles them
        assert {p for p in registered if al.refcount[p] == 0} <= set(
            al.retained)
        # registry coherence: nodes and the pid index describe each other
        assert registered == set(reg.nodes.values())

    @invariant()
    def reservations_match_slots(self):
        assert self.s.alloc.reserved == sum(
            sl.reserved_left for sl in self.s.slots)
        assert self.s.alloc.reserved <= self.s.alloc.available


TestPageAllocator = PageAllocatorMachine.TestCase
TestPagedServe = PagedServeMachine.TestCase
