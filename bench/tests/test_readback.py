"""The pool readback that the comparison reads: every leaf of the engine's
paged pool, under its own name, through a slot's page-table row, in one
program per power-of-two page count, all compiled in set-up."""
import types

import jax
import jax.numpy as jnp
import numpy as np

import smoke  # noqa: F401  (paths)
import llm


def _direct(leaf, table_row, rows, page_size):
    """Rows [0, rows) of one leaf, gathered by hand through the pages."""
    pages = np.asarray(table_row[:-(-rows // page_size)])
    x = np.asarray(leaf)[:, pages]
    return x.reshape(x.shape[0], -1, *x.shape[3:])[:, :rows]


def test_readback_on_the_smoke_engine_is_a_gather_of_every_paged_leaf():
    import repro  # noqa: F401
    from repro.serve.scheduler import Request

    spec = smoke.qwen_smoke()
    E = llm.Engine(spec, 2**31 + 5)
    E.warm_llm()
    E.snapshot()
    prompt = list(np.random.default_rng(3).integers(1, 512, 45))
    E.eng.submit(Request(rid=0, prompt=[int(t) for t in prompt],
                         max_new=6, eos=-1))
    (slot,) = E.eng.try_admit(0.0)
    for _ in range(3):
        E.eng.step(0.0)
    table_row = list(E.eng.sched.table[slot.index])
    L, g = spec["num_hidden_layers"], spec["num_key_value_heads"]
    hd = spec["hidden_size"] // spec["num_attention_heads"]
    ps = E.eng.page_size
    for rows in (1, 16, 45, 47):
        got = E.rows_of(table_row, rows)
        assert set(got) == set(E.paged_leaves()) == {"k", "v"}
        for name, x in got.items():
            assert x.shape == (L, rows, g, hd), (name, x.shape)
            want = _direct(E.eng.cache[name], table_row, rows, ps)
            assert np.array_equal(x, want), (name, rows)
        assert np.any(got["k"][:, rows - 1])     # rows the engine wrote
    assert E.retraces() == 0                      # every bucket was warmed


def test_readback_names_leaves_by_layout_not_by_a_known_name():
    """A pool of other leaves, as a latent-attention pool holds: each
    paged leaf comes back under its own key path; a leaf without the
    paged layout is left out."""
    L, n_pages, ps, n_pg = 3, 9, 4, 8
    rng = np.random.default_rng(0)
    cache = {"latent": jnp.asarray(rng.normal(size=(L, n_pages, ps, 5)),
                                   jnp.float32),
             "rope": {"k": jnp.asarray(rng.normal(size=(L, n_pages, ps, 2)),
                                       jnp.float32)},
             "lengths": jnp.zeros((L, n_pages), jnp.int32),
             "slots": jnp.zeros((L, 4, ps, 5), jnp.float32)}
    E = llm.Engine.__new__(llm.Engine)
    E.eng = types.SimpleNamespace(
        cache=cache, n_pages=n_pages, page_size=ps,
        sched=types.SimpleNamespace(n_pg=n_pg))
    E._gather = jax.jit(llm._gather_pages)
    table_row = [5, 2, 7, 1, 0, 0, 0, 0]
    got = E.rows_of(table_row, 13)
    assert set(got) == {"latent", "rope/k"}
    assert got["latent"].shape == (L, 13, 5)
    assert got["rope/k"].shape == (L, 13, 2)
    assert np.array_equal(got["latent"],
                          _direct(cache["latent"], table_row, 13, ps))
    assert np.array_equal(got["rope/k"],
                          _direct(cache["rope"]["k"], table_row, 13, ps))
    # 13 rows need 4 pages: the power-of-two bucket of 4 pages
    assert E.page_bucket(13) == 4 and E.page_bucket(17) == 8
