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
        harness.module("metrics", "decode_ms.chat")
