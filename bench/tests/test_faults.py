"""A whole run with the timed path broken underneath: ``correct`` comes
out false.  The chip check is skipped; everything after it runs, at a
CPU size.  The faults the cell can have: a served token altered where
the decode step produces it; a decode step that returns the pool
unchanged (its token's K/V rows never written); a decode step that
gathers the wrong pages (each slot reads its page-table row shifted by
one page)."""
import jax.numpy as jnp
import pytest

import smoke


def test_chat_run_is_correct_when_nothing_is_broken():
    res, err = smoke.run_smoke("qmoe.chat-over", smoke.qwen_smoke(),
                               smoke.chat_smoke(), seconds=2.0)
    assert res["correct"], err
    assert res["checks"]["rows_compared"]["value"] > 0
    assert err.strip().splitlines()[-1].startswith("check retraces")
    assert list(res)[-1] == "checks"


def _altered(good):
    def step(self, *a):
        nxt, cache = good(self, *a)
        return (nxt + 1) % self.cfg.vocab, cache
    return step


def _unchanged(good):
    def step(self, params, cache, *a):
        nxt, _ = good(self, params, cache, *a)
        return nxt, cache
    return step


def _wrong_pages(good):
    def step(self, params, cache, tokens, pos, pages):
        return good(self, params, cache, tokens, pos,
                    jnp.roll(pages, 1, axis=1))
    return step


@pytest.mark.parametrize("fault,check", [
    (_altered, "max_logit_gap"),
    (_unchanged, "kv_decode_err"),
    (_wrong_pages, "kv_decode_err"),
])
def test_a_broken_decode_step_makes_the_run_incorrect(monkeypatch, fault,
                                                      check):
    from repro.serve.batcher import ContinuousBatcher

    good = ContinuousBatcher._decode_paged_impl
    monkeypatch.setattr(ContinuousBatcher, "_decode_paged_impl", fault(good))
    res, err = smoke.run_smoke("qmoe.chat-over", smoke.qwen_smoke(),
                               smoke.chat_smoke(), seconds=2.5)
    assert not res["correct"]
    assert not res["checks"][check]["ok"], res["checks"]
    assert f"check {check}" in err and "FAILED" in err
