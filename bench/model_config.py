"""A configuration file's published keys, mapped onto the program's
``ModelConfig``, and the engine settings every LLM cell derives from it.

The file under ``bench/configs/`` holds the model as it runs, under the
keys of its published ``config.json``; its ``engine`` group holds the
serving settings.  The keys are translated by the module of the file's
``model_type``, ``bench/families/<model_type>.py``, found by that name:
a later configuration, of a family already here or of a new one, comes
in with new files alone.
"""
from __future__ import annotations

import harness


def model_config(spec: dict):
    """``repro.models.config.ModelConfig`` for a configuration file, from
    the ``model_config(spec)`` of its family's module; with no such module,
    a ``SetupError`` that names the file to add."""
    return harness.module("families", spec["model_type"]).model_config(spec)


def buckets(eng: dict) -> tuple[int, ...]:
    """Power-of-two prefill buckets from ``bucket_lo`` up to ``cache_len``."""
    out, b = [], int(eng["bucket_lo"])
    while b < eng["cache_len"]:
        out.append(b)
        b *= 2
    out.append(int(eng["cache_len"]))
    return tuple(out)


def expert_capacity(cfg, s: int) -> int:
    """The program's per-row expert capacity at ``s`` tokens
    (``models/moe.py``), repeated here so a test can hold it to s."""
    return max(1, int(s * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
