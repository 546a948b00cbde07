"""moonshot-v1-16b-a3b [moe]: Moonlight-16B-A3B (model_type deepseek_v3).
27L d=2048 16H of latent attention (kv_lora_rank 512, qk nope 128 + rope
64, v 128, no q compression), layer 0 dense (ff 11264), layers 1-26 with
64 routed experts top-6 (expert ff 1408, sigmoid router with correction
bias, noaux_tc in one group, routed scale 2.446) + 2 shared, untied
vocab 163840, rope theta 50000, rms eps 1e-5.
[hf:moonshotai/Moonlight-16B-A3B config.json]"""
from repro.configs import pad_vocab
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv=16,
    head_dim=128,
    d_ff=11264,
    vocab=pad_vocab(163840),  # 163840 (aligned)
    act="swiglu",
    rope_theta=50000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    attn="mla",
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    n_experts=64,
    top_k=6,
    n_shared=2,
    expert_dff=1408,
    first_dense=1,
    router="sigmoid_bias",
    routed_scale=2.446,
)
