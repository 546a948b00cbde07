"""``model_type`` ``deepseek_v3``: the published keys of a DeepSeek-V3
``config.json`` (Moonlight-16B-A3B), mapped onto the program's
``ModelConfig``.

Latent attention (``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``) without q compression; the first
``first_k_dense_replace`` layers dense of width ``intermediate_size``,
then an expert layer every layer; the sigmoid ``noaux_tc`` router in one
group, normalised and scaled by ``routed_scaling_factor``; the shared
experts one block of ``n_shared_experts`` times the routed width.  A
file that asks for what the program does not compute is refused here.
"""
from __future__ import annotations

# published settings the program computes only one way
FIXED = {"q_lora_rank": None, "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
         "norm_topk_prob": True, "moe_layer_freq": 1, "attention_bias": False,
         "hidden_act": "silu"}


def model_config(spec: dict):
    """``repro.models.config.ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    bad = {k: spec.get(k) for k, v in FIXED.items() if spec.get(k) != v}
    if bad:
        raise ValueError(f"{spec['name']}: the program computes "
                         f"deepseek_v3 only with {FIXED}; the file has {bad}")
    eng = spec["engine"]
    return ModelConfig(
        name=spec["name"],
        family="moe",
        n_layers=spec["num_hidden_layers"],
        d_model=spec["hidden_size"],
        n_heads=spec["num_attention_heads"],
        n_kv=spec["num_key_value_heads"],
        head_dim=spec["v_head_dim"],
        d_ff=spec["intermediate_size"],
        vocab=spec["vocab_size"],
        act="swiglu",
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        attn="mla",
        kv_lora_rank=spec["kv_lora_rank"],
        qk_nope_dim=spec["qk_nope_head_dim"],
        qk_rope_dim=spec["qk_rope_head_dim"],
        v_head_dim=spec["v_head_dim"],
        n_experts=spec["n_routed_experts"],
        top_k=spec["num_experts_per_tok"],
        n_shared=spec["n_shared_experts"],
        expert_dff=spec["moe_intermediate_size"],
        first_dense=spec["first_k_dense_replace"],
        router="sigmoid_bias",
        routed_scale=float(spec["routed_scaling_factor"]),
        capacity_factor=float(eng["capacity_factor"]),
        dtype=eng["dtype"],
        param_dtype=eng["param_dtype"],
        remat=False,
        zero1=False,
    ).validate()
