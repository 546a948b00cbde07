"""A configuration's published keys reach the program through the module
of its ``model_type``, found by name."""
import pytest

import smoke  # noqa: F401  (paths)
import harness
import model_config


def test_qwen2_moe_family_gives_the_cells_model_config():
    spec = harness.data_file("configs", "qwen1.5-moe-a2.7b")
    cfg = harness.module("families", "qwen2_moe").model_config(spec)
    assert model_config.model_config(spec) == cfg
    assert cfg.name == "qwen1.5-moe-a2.7b" and cfg.family == "moe"
    assert cfg.n_layers == 8 and cfg.d_model == 2048
    assert cfg.n_heads == 16 and cfg.n_kv == 16 and cfg.head_dim == 128
    assert cfg.n_experts == 60 and cfg.top_k == 4
    assert cfg.n_shared == 4 and cfg.expert_dff == 1408
    assert cfg.d_ff == 1408 and cfg.vocab == 151936
    assert cfg.act == "swiglu" and cfg.norm_eps == 1e-6
    assert cfg.rope_theta == 1e6 and cfg.tie_embeddings is True
    assert cfg.capacity_factor == 15.0
    assert cfg.dtype == "bfloat16" and cfg.param_dtype == "bfloat16"
    assert cfg.remat is False and cfg.zero1 is False


def test_a_model_type_with_no_family_names_the_file_to_add():
    spec = dict(harness.data_file("configs", "qwen1.5-moe-a2.7b"),
                model_type="no_such_family")
    with pytest.raises(harness.SetupError,
                       match="bench/families/no_such_family.py"):
        model_config.model_config(spec)
