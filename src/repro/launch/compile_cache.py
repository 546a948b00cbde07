"""JAX's persistent compilation cache, placed at a fixed path.

Entry points (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``)
call ``enable_compile_cache()`` first thing in ``main``; importing this
module changes nothing.  The directory is part of the cache's key, so it
never moves: ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the
variable itself and this leaves it alone), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE", "enable_compile_cache"]

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
