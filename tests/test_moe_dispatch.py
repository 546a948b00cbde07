"""The two forms of the routed-expert dispatch (models/moe.py).

At a dropless capacity (C >= s) the grouped form, which multiplies each
expert only by the rows routed to it, must give what the (E, C, d) buffer
form gives; below it the buffer form keeps dropping, per row, the
assignments past C.  ``dispatch_form`` picks the form from the token width
alone, and the engine counts its launches by form.

Tolerance: float32 on the CPU; the two forms compute the same products
and add a token's K expert outputs in the same order, and differ only in
how each matmul orders its sums.  That is a few ulps of values of order
1: 1e-5 relative.  A wrong row, gate or expert moves the output by 1e-2
or more.
"""
import dataclasses
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import repro  # noqa: F401
from repro.configs import get_config
from repro.dist.act_sharding import use_mesh
from repro.models.moe import capacity, dispatch_form, init_moe, moe_forward, route
from repro.serve.batcher import ContinuousBatcher
from repro.serve.scheduler import Request

TOL = 1e-5
BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")

# (preset, routed experts, top-k, the benchmark's dropless capacity factor)
ROUTERS = {
    "softmax": ("qwen2-moe-a2.7b", 60, 4, 15.0),
    "sigmoid_bias": ("moonshot-v1-16b-a3b", 64, 6, 11.0),
}


def _cfg(router, capacity_factor=None):
    """The preset's smoke widths with its published expert count and
    top-k, float32."""
    name, E, K, cf = ROUTERS[router]
    c = dataclasses.replace(
        get_config(name).smoke(), n_experts=E, top_k=K,
        capacity_factor=cf if capacity_factor is None else capacity_factor,
        dtype="float32", param_dtype="float32").validate()
    assert c.router == router
    return c


def _forward(p, cfg, x, form):
    """``moe_forward`` in the named form: an active mesh keeps the buffer
    form, whatever the capacity."""
    if form == "grouped":
        return moe_forward(p, cfg, x)[0]
    with use_mesh(Mesh(np.array(jax.devices()[:1]), ("data",))):
        return moe_forward(p, cfg, x)[0]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / np.max(np.abs(b))


@pytest.mark.parametrize("router,s,starve", [
    ("softmax", 2, False), ("softmax", 16, False), ("softmax", 67, False),
    ("sigmoid_bias", 2, False), ("sigmoid_bias", 16, False),
    ("sigmoid_bias", 67, False), ("softmax", 16, True),
    ("sigmoid_bias", 16, True),
])
def test_grouped_equals_buffer_at_dropless_capacity(router, s, starve):
    cfg = _cfg(router)
    assert capacity(cfg, s) >= s and dispatch_form(cfg, s) == "grouped"
    p = init_moe(jax.random.key(s), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(100 + s), (2, s, cfg.d_model),
                          jnp.float32)
    if starve:
        # positive features against a negative column: expert 0's score is
        # the lowest of every token's, so it receives no token
        x = jnp.abs(x)
        p = dict(p, router=p["router"].at[:, 0].set(-1.0))
        assert not (np.asarray(route(p, cfg, x)[1]) == 0).any()
    grouped = _forward(p, cfg, x, "grouped")
    buffer = _forward(p, cfg, x, "buffer")
    assert grouped.dtype == buffer.dtype == jnp.float32
    assert _rel(grouped, buffer) <= TOL


@pytest.mark.parametrize("router", ["softmax", "sigmoid_bias"])
def test_buffer_form_drops_assignments_past_capacity(router):
    """At the training capacity factor 1.25 the buffer form keeps, per row
    and expert, the first C assignments in (token, k) order and drops the
    rest: a NumPy loop of that rule gives the same output."""
    cfg = _cfg(router, capacity_factor=1.25)
    b, s = 2, 24
    C = capacity(cfg, s)
    assert dispatch_form(cfg, s) == "buffer" and C < s
    p = init_moe(jax.random.key(7), cfg, jnp.float32)
    # a quarter of the experts' columns lifted: they overflow
    p = dict(p, router=p["router"].at[:, : cfg.n_experts // 4].add(0.3))
    x = jnp.abs(jax.random.normal(jax.random.key(8), (b, s, cfg.d_model),
                                  jnp.float32))
    y, _ = moe_forward(p, cfg, x)

    gates, idx, _ = route(p, cfg, x)
    gates, idx = np.asarray(gates, np.float64), np.asarray(idx)
    xs = np.asarray(x, np.float64)
    wi, wo = np.asarray(p["wi"], np.float64), np.asarray(p["wo"], np.float64)
    silu = lambda v: v / (1 + np.exp(-v))
    us = np.einsum("bsd,dgf->bsgf", xs, np.asarray(p["shared_wi"], np.float64))
    want = (silu(us[..., 0, :]) * us[..., 1, :]) @ np.asarray(p["shared_wo"])
    dropped = 0
    for r in range(b):
        taken = np.zeros(cfg.n_experts, int)
        for t in range(s):
            for k in range(cfg.top_k):
                e = idx[r, t, k]
                taken[e] += 1
                if taken[e] > C:
                    dropped += 1
                    continue
                u = np.einsum("d,dgf->gf", xs[r, t], wi[e])
                want[r, t] += gates[r, t, k] * (silu(u[0]) * u[1]) @ wo[e]
    assert dropped > 0
    assert _rel(y, want) <= TOL


def _bench_engines():
    """(config file name, ModelConfig, prefill bucket widths, prefill chunk)
    of each LLM configuration the benchmark serves."""
    out = []
    for fn in sorted(os.listdir(os.path.join(BENCH, "configs"))):
        with open(os.path.join(BENCH, "configs", fn)) as fh:
            spec = json.load(fh)
        path = os.path.join(BENCH, "families", spec["model_type"] + ".py")
        mod_spec = importlib.util.spec_from_file_location("_family", path)
        family = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(family)
        eng = spec["engine"]
        widths, w = [], int(eng["bucket_lo"])
        while w < eng["cache_len"]:
            widths.append(w)
            w *= 2
        widths.append(int(eng["cache_len"]))
        out.append((fn, family.model_config(spec), widths,
                    int(eng["prefill_chunk"])))
    return out


def test_dispatch_form_of_the_benchmark_engines():
    """Every extend width the benchmark's engines launch runs the grouped
    form; the decode step and the training capacity keep the buffer."""
    engines = _bench_engines()
    assert len(engines) == 2
    for name, cfg, widths, chunk in engines:
        assert cfg.family == "moe", name
        for w in widths + [chunk]:
            assert dispatch_form(cfg, w) == "grouped", (name, w)
        assert dispatch_form(cfg, 1) == "buffer", name
        train = dataclasses.replace(cfg, capacity_factor=1.25)
        for w in widths:
            assert dispatch_form(train, w) == "buffer", (name, w)


@pytest.mark.parametrize("page_size", [8, 16])
def test_engine_counts_launches_by_form(page_size):
    """An engine at a dropless capacity counts each extend launch as
    grouped and each decode step as buffer."""
    cfg = dataclasses.replace(_cfg("sigmoid_bias"), n_experts=8, top_k=2)
    from repro.models import init_params

    eng = ContinuousBatcher(cfg, init_params(cfg, jax.random.key(0)),
                            n_slots=2, cache_len=32, prefill_chunk=8,
                            prefill_buckets=(8, 16), page_size=page_size)
    steps = 0
    real_step = eng._decode_fn

    def counted(*a):
        nonlocal steps
        steps += 1
        return real_step(*a)

    eng._decode_fn = counted
    for rid, plen in enumerate((5, 12, 3)):
        eng.submit(Request(rid=rid, prompt=list(range(1, plen + 1)),
                           max_new=3, eos=-1))
    eng.run_to_completion()
    assert eng.moe_stats() == {"grouped": 3, "buffer": steps}
    assert steps > 0
