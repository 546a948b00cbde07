"""A configuration file's published keys, mapped onto the program's
``ModelConfig``.

The file under ``bench/configs/`` holds the model as it runs, under the
keys of its published ``config.json``; its ``engine`` group holds the
serving settings.  This module is the one place that translates those
keys, so that a later configuration of the same family needs only a new
file.
"""
from __future__ import annotations

FAMILIES = {"qwen2_moe": "moe"}


def model_config(spec: dict):
    """``repro.models.config.ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    eng = spec["engine"]
    heads = spec["num_attention_heads"]
    return ModelConfig(
        name=spec["name"],
        family=FAMILIES[spec["model_type"]],
        n_layers=spec["num_hidden_layers"],
        d_model=spec["hidden_size"],
        n_heads=heads,
        n_kv=spec["num_key_value_heads"],
        head_dim=spec["hidden_size"] // heads,
        d_ff=spec["moe_intermediate_size"],
        vocab=spec["vocab_size"],
        act="swiglu" if spec["hidden_act"] == "silu" else "geglu",
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        n_experts=spec["num_experts"],
        top_k=spec["num_experts_per_tok"],
        n_shared=(spec["shared_expert_intermediate_size"]
                  // spec["moe_intermediate_size"]),
        expert_dff=spec["moe_intermediate_size"],
        capacity_factor=float(eng["capacity_factor"]),
        dtype=eng["dtype"],
        param_dtype=eng["param_dtype"],
        remat=False,
        zero1=False,
    ).validate()


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
