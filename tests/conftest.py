"""Shared test configuration and serve-engine fixtures.

Puts ``src/`` on sys.path so a bare ``pytest`` works without PYTHONPATH, and
documents the optional dev dependency policy: suites that use hypothesis
guard their own import with ``pytest.importorskip`` so a missing optional
dependency reports as an explicit SKIP, never a collection ERROR.

The serve suites (``test_serve_batcher``/``test_serve_paged``/
``test_serve_offline``/``test_serve_soak``) share one smoke model and one
engine factory from here instead of keeping per-file copies: ``cfg``/
``params`` are session-scoped fixtures, and ``make_engine`` builds a
``ContinuousBatcher`` over the shared geometry, bucketed or chunked.
``snapshot_rows``/``logical_rows`` read a request's written KV span back
out of the paged pool.  ``reference`` is what the engines are held to: a
plain batch-1 greedy loop over ``repro.models`` (prefill, then one
``decode_step`` per token) with no slots, pages or splice, sharing no code
with ``repro.serve``.  Its tokens must equal the engine's; its K/V rows
must agree within ``KV_ATOL``/``KV_RTOL``, since the engine's graphs (a
paged gather, a chunked or padded extend) order their float32 sums
differently from the reference's dense prefill.
"""
import functools
import os
import sys

import pytest

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

# one geometry for every serve suite: 4 pages per slot, page == chunk
CACHE_LEN = 32
CHUNK = 8
PAGE = 8
N_PG = CACHE_LEN // PAGE

# float32 tolerance of an engine's K/V rows against ``reference``: the
# largest gap measured on the CPU over chunked, padded-bucket and
# shared-prefix admissions is 2.98e-7 on rows of magnitude up to 0.83
# (a few float32 ulps); a row written to the wrong page or position is
# off by O(1)
KV_ATOL = 1e-5
KV_RTOL = 1e-5


@pytest.fixture(scope="session")
def cfg():
    from repro.configs import get_config

    return get_config("gemma-2b").smoke()


@pytest.fixture(scope="session")
def params(cfg):
    import jax

    from repro.models import init_params

    return init_params(cfg, jax.random.key(0))


def make_engine(cfg, params, *, buckets=None, **kw):
    """Engine factory over the shared serve geometry (pages of PAGE unless
    overridden); ``buckets`` arms length-bucketed prefill, which
    exercises the padded write barrier (DESIGN.md §13)."""
    from repro.serve.batcher import ContinuousBatcher

    kw.setdefault("n_slots", 3)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("page_size", PAGE)
    if buckets is not None:
        kw.setdefault("prefill_buckets", buckets)
    return ContinuousBatcher(cfg, params, **kw)


def logical_rows(eng, table_row):
    """Gather one slot's logical rows ``{leaf: (L, cache_len, ...)}`` out
    of the paged pool through a page-table row snapshot."""
    import numpy as np

    pages = np.asarray(table_row)
    rows = {}
    for name, leaf in eng.cache.items():
        if getattr(leaf, "ndim", 0) < 3:
            continue
        pool = np.asarray(leaf)  # (L, P, page, ...)
        got = pool[:, pages]
        rows[name] = got.reshape(got.shape[0], -1, *got.shape[3:])
    return rows


def snapshot_rows(eng):
    """Capture every LLM request's written KV span [0, plen+n_out-1) AT
    RETIREMENT — the one moment the span is complete and the slot's
    page-table row is still mapped — so requests can be checked even
    under slot churn and page reuse.  Returns the ``{rid: {leaf: rows}}``
    dict the engine fills as it runs."""
    rows = {}
    orig = eng.sched.record_token

    def spy(slot, token, now=0.0):
        req, idx = slot.req, slot.index
        done = orig(slot, token, now)
        if done:
            end = len(req.prompt) + len(req.out) - 1  # last written + 1
            got = logical_rows(eng, eng.sched.table[idx])
            rows[req.rid] = {n: x[:, :end].copy() for n, x in got.items()}
        return done

    eng.sched.record_token = spy
    return rows


def run_with_row_snapshots(eng, reqs):
    """Submit ``reqs``, run to completion, and return ({rid: retired
    Request}, {rid: {leaf: rows}}) with the rows of ``snapshot_rows``."""
    rows = snapshot_rows(eng)
    for r in reqs:
        eng.submit(r)
    done = eng.run_to_completion()
    return {r.rid: r for r in done}, rows


@functools.lru_cache(maxsize=None)
def _reference_fns(cfg, cache_len):
    import jax

    from repro.models import decode_step, prefill

    pre = jax.jit(lambda p, toks: prefill(cfg, p, {"tokens": toks},
                                          cache_len))
    dec = jax.jit(lambda p, c, tok, pos: decode_step(cfg, p, c, tok, pos))
    return pre, dec


def reference(cfg, params, prompt, max_new, eos=None, cache_len=CACHE_LEN):
    """Plain batch-1 greedy generation: ``prefill`` of the prompt, then
    one ``decode_step`` per further token, stopping at ``max_new`` tokens
    or at ``eos``.  Returns (tokens, {leaf: (L, plen+n_out-1, ...)}): the
    rows the loop wrote, prompt and decoded tokens alike."""
    import jax.numpy as jnp
    import numpy as np

    pre, dec = _reference_fns(cfg, cache_len)
    prompt = [int(t) for t in prompt]
    logits, cache = pre(params, jnp.asarray([prompt], jnp.int32))
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < max_new and (eos is None or out[-1] != eos):
        pos = len(prompt) + len(out) - 1
        logits, cache = dec(params, cache,
                            jnp.asarray([[out[-1]]], jnp.int32),
                            jnp.int32(pos))
        out.append(int(jnp.argmax(logits[0])))
    end = len(prompt) + len(out) - 1
    rows = {n: np.asarray(x)[:, 0, :end] for n, x in cache.items()
            if getattr(x, "ndim", 0) >= 3}
    return out, rows


def check_against_reference(cfg, params, req, rows=None):
    """Hold one retired request to ``reference``: the same tokens, and
    (given its snapshot ``rows``) every leaf's K/V rows within the stated
    float32 tolerance."""
    import numpy as np

    want, want_rows = reference(cfg, params, req.prompt, req.max_new,
                                eos=req.eos)
    assert list(req.out) == want, f"rid {req.rid} tokens"
    if rows is not None:
        assert sorted(rows) == sorted(want_rows)
        for name, x in rows.items():
            np.testing.assert_allclose(x, want_rows[name], rtol=KV_RTOL,
                                       atol=KV_ATOL,
                                       err_msg=f"rid {req.rid} {name}")
