"""``model_type`` ``qwen2_moe``: the published keys of a Qwen2-MoE
``config.json`` (Qwen1.5-MoE), mapped onto the program's ``ModelConfig``.

The shared experts are one block of width
``shared_expert_intermediate_size``, which the program holds as
``n_shared`` experts of the routed width; every head is
``hidden_size // num_attention_heads`` wide.
"""
from __future__ import annotations


def model_config(spec: dict):
    """``repro.models.config.ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    eng = spec["engine"]
    heads = spec["num_attention_heads"]
    return ModelConfig(
        name=spec["name"],
        family="moe",
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
