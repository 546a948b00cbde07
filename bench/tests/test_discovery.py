"""A new cell, traffic mix and per-layer metric are added with files and
``BENCHMARK.json`` entries alone: nothing already in ``bench/`` changes."""
import json
import shutil

import smoke
import harness


def test_new_cell_mix_and_metric_from_files_alone(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    spec = json.loads(harness.SPEC_FILE.read_text())
    # the new files: a mix, a metric reader, and the entries that name them
    mix = smoke.chat_smoke()
    mix["rate_per_s"] = 5.0
    (bench / "traffic" / "tiny-chat.json").write_text(json.dumps(mix))
    (bench / "metrics" / "requests_seen.tiny.py").write_text(
        "def read(run):\n    return float(len(run.window.reqs)) or None\n")
    spec["workloads"].append({"name": "qmoe.tiny", "config":
                              "qwen1.5-moe-a2.7b", "traffic": "tiny-chat",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "requests_seen.tiny", "unit": "1",
                              "better": "higher", "source": "program_counter",
                              "layer": "scheduler", "moves": "tokens_per_s",
                              "workloads": ["qmoe.tiny"]})
    spec["end_to_end"][0]["workloads"].append("qmoe.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(harness, "BENCH", bench)
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "SPEC_FILE", root / "BENCHMARK.json")

    names = [m["name"] for m in harness.cell_metrics(
        harness.load_spec(), "qmoe.tiny", trace=True)]
    assert names == ["requests_seen.tiny"]
    assert harness.data_file("traffic", "tiny-chat")["rate_per_s"] == 5.0
    res, _ = smoke.run_smoke("qmoe.tiny", smoke.qwen_smoke(),
                             harness.data_file("traffic", "tiny-chat"),
                             seconds=1.5, trace=1)
    assert res["correct"]
    assert res["metrics"]["requests_seen.tiny"]["value"] >= 1
    res, _ = smoke.run_smoke("qmoe.tiny", smoke.qwen_smoke(),
                             harness.data_file("traffic", "tiny-chat"),
                             seconds=1.5, trace=0)
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    after = {p: p.read_bytes() for p in before}
    assert after == before  # no file that was there changed


FAMILY = '''"""A family no cell runs: its own key names."""


def model_config(spec):
    from repro.models.config import ModelConfig

    eng = spec["engine"]
    return ModelConfig(
        name=spec["name"], family="moe", n_layers=spec["num_hidden_layers"],
        d_model=spec["hidden_size"], n_heads=spec["num_attention_heads"],
        n_kv=spec["num_key_value_heads"], head_dim=spec["head_dim"],
        d_ff=spec["moe_intermediate_size"], vocab=spec["vocab_size"],
        act="swiglu", rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]), tie_embeddings=True,
        n_experts=spec["n_routed_experts"],
        top_k=spec["num_experts_per_tok"],
        n_shared=spec["n_shared_experts"],
        expert_dff=spec["moe_intermediate_size"],
        capacity_factor=float(eng["capacity_factor"]),
        dtype=eng["dtype"], param_dtype=eng["param_dtype"],
        remat=False, zero1=False).validate()
'''

REFERENCE = '''"""The plain reference of a family no cell runs: the Qwen reference
over the same weights, its keys renamed."""
import harness

QWEN = harness.module("reference", "qwen1.5-moe-a2.7b")
KV_PROMPT_LIMIT = QWEN.KV_PROMPT_LIMIT
KV_DECODE_LIMIT = QWEN.KV_DECODE_LIMIT
GAP_LIMIT = QWEN.GAP_LIMIT


def readings(spec, seed, seqs, control=False):
    s = dict(spec, num_experts=spec["n_routed_experts"],
             shared_expert_intermediate_size=spec["n_shared_experts"]
             * spec["moe_intermediate_size"])
    return QWEN.readings(s, seed, seqs, control)
'''


def test_new_family_config_reference_and_cell_from_files_alone(
        tmp_path, monkeypatch):
    """A configuration of a ``model_type`` no family module knows, under
    key names the Qwen file does not use, comes in with its family
    module, its reference and a cell entry; a run of it is correct with
    the trace off and on, and no file that was there changes."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    spec = json.loads(harness.SPEC_FILE.read_text())
    qwen = smoke.qwen_smoke()
    cfg = {"name": "tiny-other", "model_type": "tiny_other",
           "hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 4, "head_dim": 16,
           "num_hidden_layers": 2, "vocab_size": 512,
           "n_routed_experts": 8, "num_experts_per_tok": 2,
           "n_shared_experts": 2, "moe_intermediate_size": 32,
           "rope_theta": qwen["rope_theta"],
           "rms_norm_eps": qwen["rms_norm_eps"], "engine": qwen["engine"]}
    assert "n_routed_experts" not in qwen and "num_experts" not in cfg
    (bench / "configs" / "tiny-other.json").write_text(json.dumps(cfg))
    (bench / "families" / "tiny_other.py").write_text(FAMILY)
    (bench / "reference" / "tiny-other.py").write_text(REFERENCE)
    spec["configs"].append({"name": "tiny-other", "source": "test",
                            "file": "bench/configs/tiny-other.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "other.chat", "config": "tiny-other",
                              "traffic": "chat-over", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("other.chat")
    # family readers: a new cell's metrics need entries only
    for fam in ("slot_occupancy", "device_idle"):
        spec["per_layer"].append({"name": f"{fam}.other", "unit": "%",
                                  "better": "higher",
                                  "source": "device_trace",
                                  "layer": "scheduler",
                                  "moves": "tokens_per_s",
                                  "workloads": ["other.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(harness, "BENCH", bench)
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "SPEC_FILE", root / "BENCHMARK.json")

    import model_config

    got = model_config.model_config(harness.data_file("configs",
                                                      "tiny-other"))
    assert (got.n_experts, got.n_shared, got.head_dim) == (8, 2, 16)
    res, err = smoke.run_smoke(
        "other.chat", harness.data_file("configs", "tiny-other"),
        smoke.chat_smoke(), seconds=2.0, trace=0)
    assert res["correct"], err
    assert res["checks"]["rows_compared"]["value"] > 0
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    res, err = smoke.run_smoke(
        "other.chat", harness.data_file("configs", "tiny-other"),
        smoke.chat_smoke(), seconds=2.0, trace=1)
    assert res["correct"], err
    assert "slot_occupancy.other" in res["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before  # no file that was there changed


def test_every_named_file_exists():
    spec = harness.load_spec()
    for c in spec["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
        assert harness.data_file("configs", c["name"])["name"] == c["name"]
    for w in spec["workloads"]:
        harness.data_file("traffic", w["traffic"])
        harness.data_file("configs", w["config"])
        harness.module("reference", w["config"])
        assert harness.cell_metrics(spec, w["name"], True)
        assert len(harness.cell_metrics(spec, w["name"], False)) >= 2
    for m in spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)


def test_a_metric_family_shares_one_reader():
    assert harness.metric_reader("device_idle.chat") is \
        harness.module("metrics", "device_idle")
    assert harness.metric_reader("decode_ms.chat") is \
        harness.module("metrics", "decode_ms")
