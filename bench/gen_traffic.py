"""The one traffic generator: every mix under ``bench/traffic/`` is a JSON
file of parameters that this module reads.

Every seed gets the same work: the schedule (arrival gaps and lengths, in
their order) is drawn from the mix's own ``schedule_seed``, and only the
token ids from the run's seed, so that a run's rate does not hang on
which lengths its seed happened to put in flight together.  Sizes and
gaps are drawn in blocks of ``block`` items; each block holds the same
``block`` quantiles of the distribution (at (i + 0.5) / block) in a
shuffled order, so that a short stretch of the schedule already carries
the distribution's shape.

Kinds:

* ``open_loop``: independent requests, Poisson arrivals at ``rate_per_s``,
  lognormal prompt and output lengths (``median``, ``sigma``, clipped to
  ``[min, max]``), every prompt distinct.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_N01 = NormalDist()


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, from any whole-number seed."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
    words += [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def _quantiles(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def lognormal_block(rng, dist: dict, block: int) -> np.ndarray:
    """``block`` clipped lognormal lengths (stratified), shuffled."""
    z = np.array([_N01.inv_cdf(q) for q in _quantiles(block)])
    v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    v = np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)
    return rng.permutation(v)


def exponential_block(rng, mean: float, block: int) -> np.ndarray:
    return rng.permutation(-mean * np.log1p(-_quantiles(block)))


def _blocks(fn, n: int, block: int) -> np.ndarray:
    k = -(-n // block)
    return np.concatenate([fn() for _ in range(k)])[:n]


def open_loop(spec: dict, seed: int, vocab: int, seconds: float) -> list:
    """[{rid, due, prompt, max_new}] with ``due`` in seconds from the
    window's start, covering the window with a spare block."""
    rate, block = float(spec["rate_per_s"]), int(spec["block"])
    n = (int(rate * seconds / block) + 2) * block
    r = rng_for(int(spec["schedule_seed"]), "open_loop")
    gaps = _blocks(lambda: exponential_block(r, 1.0 / rate, block), n, block)
    plen = _blocks(lambda: lognormal_block(r, spec["prompt"], block), n, block)
    olen = _blocks(lambda: lognormal_block(r, spec["output"], block), n, block)
    due = np.cumsum(gaps) - gaps[0]
    tok = rng_for(seed, "tokens")
    return [{"rid": i, "due": float(due[i]),
             "prompt": tok.integers(1, vocab, int(plen[i])).tolist(),
             "max_new": int(olen[i])} for i in range(n)]
