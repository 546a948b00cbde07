"""Size the paged KV pool of an LLM configuration for one v5e chip.

Compiles the engine's paged decode step and its widest bucketed extend
for a described (not attached) v5e and prints what each program needs.
A step holds the weights, its temporaries and the pool twice (the pool is
not donated); the pool gets what the larger step leaves of the chip's
memory, less a margin, in whole multiples of 64 pages.
Run on a host with the TPU compiler installed and no chip needed:

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/size_pool.py bench/configs/<config>.json
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import model_config  # noqa: E402

HBM = 16909336064   # bytes_limit of one v5e chip, as memory_stats reports it
MARGIN = 1 << 30


def main(path: str) -> None:
    import repro  # noqa: F401
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.models import abstract_params, decode_step, extend_step
    from repro.serve.serve_step import paged_pool_abstract

    jax.config.update("jax_enable_compilation_cache", False)
    spec = json.load(open(path))
    eng = spec["engine"]
    cfg = model_config.model_config(spec)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    params = abstract_params(cfg)
    slots, clen, ps = eng["n_slots"], eng["cache_len"], eng["page_size"]
    n_pg = clen // ps
    probe_pages = 1 + n_pg + 2

    def place(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sh),
            tree)

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sh)

    pool = paged_pool_abstract(cfg, params, probe_pages, ps)
    page_bytes = sum(l.size * l.dtype.itemsize for l in
                     jax.tree_util.tree_leaves(pool)) / probe_pages

    def dec(p, c, t, pos, pages):
        logits, c = decode_step(cfg, p, c, t, pos, pages=pages, page_size=ps)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), c

    def ext(p, c, t, pos, idx, pages, valid, scratch):
        return extend_step(cfg, p, c, t, pos, logit_index=idx, pages=pages,
                           page_size=ps, valid_len=valid, scratch=scratch)

    need = {}
    for name, fn, args in (
        ("decode", dec, (S(slots, 1), S(slots), S(slots, n_pg))),
        ("extend", ext, (S(1, clen), S(), S(), S(1, n_pg), S(), S())),
    ):
        c = jax.jit(fn, donate_argnums=()).lower(
            place(params), place(pool), *args).compile()
        m = c.memory_analysis()
        need[name] = dict(arg=m.argument_size_in_bytes,
                          out=m.output_size_in_bytes,
                          temp=m.temp_size_in_bytes,
                          alias=m.alias_size_in_bytes)
        print(name, {k: round(v / 2**30, 3) for k, v in need[name].items()},
              "GiB", flush=True)
    w = sum(l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(params))
    # the pool is an argument and an output of every step (not donated),
    # so a step holds it twice beside its temporaries
    worst = max(v["temp"] + v["out"] - probe_pages * page_bytes
                for v in need.values())
    free = HBM - w - worst - MARGIN
    n_pages = int(free // (2 * page_bytes)) // 64 * 64
    print(json.dumps({"weights_bytes": w, "page_bytes": page_bytes,
                      "worst_step_bytes": worst, "n_pages": n_pages}))


if __name__ == "__main__":
    main(sys.argv[1])
