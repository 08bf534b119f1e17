"""Every cell, configuration, mix and metric of ``BENCHMARK.json`` loads by
name, and a new one is added with new files and entries alone."""
import copy
import json
import shutil

import pytest

from chipbench_helpers import CHIP, ROOT, write_tree

from harness import spec

BENCH = spec.load_benchmark()
CONTRACT_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}


def test_benchmark_has_the_contract_keys_and_cells():
    assert set(BENCH) == CONTRACT_KEYS
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert [w["name"] for w in BENCH["workloads"]] == [
        "qwen3-8b-4L.chat", "mistral-nemo-12b-4L.reason"]
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert names == ["output_tok_s", "ttft_p95_ms", "itl_p50_ms",
                     "setup_s"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert len(BENCH["per_layer"]) == 8


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_by_name(cell):
    c = spec.load_cell(cell)
    assert c.chips == 1
    assert c.params["slots"] > 0 and c.params["max_logit_gap"] > 0
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    if c.traffic["loop"] == "open":
        assert c.params["rate_per_s"] > 0


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_source_cut_and_deployment(cfg):
    with open(ROOT / cfg["file"]) as f:
        c = json.load(f)
    assert c["name"] == cfg["name"] and c["source"] == cfg["source"]
    assert sorted(c["reduced"]) == sorted(cfg["reduced"])
    for k, v in c["reduced"].items():
        assert c[k] == v["here"] != v["published"]
    assert isinstance(c["assumed"], dict)
    assert {"chips_per_layer", "stages", "stands_for"} <= set(c["deployment"])
    assert c["chips"] in (1, 4)


def test_per_layer_metrics_name_a_reported_end_to_end_metric():
    for m in BENCH["per_layer"]:
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        for w in m.get("workloads", [x["name"] for x in BENCH["workloads"]]):
            assert "workloads" not in moved or w in moved["workloads"]


def test_a_new_cell_is_data_only(tmp_path):
    """A configuration, a mix, a metric and a cell added as new files
    and new entries; no existing file changes."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP / "metrics", chip / "metrics")
    bench = copy.deepcopy(BENCH)
    base = json.loads((CHIP / "configs" / "qwen3-8b-4L.json").read_text())
    files = {
        "benchmarks/chip/configs/other-8L.json": dict(
            base, name="other-8L", num_hidden_layers=8),
        "benchmarks/chip/traffic/steady.json": {
            "name": "steady", "loop": "open",
            "arrivals": {"process": "gamma", "cv": 1.0},
            "prompt": {"median": 128, "sigma": 0.5, "snap": [128, 256]},
            "output": {"median": 64, "sigma": 0.5, "min": 8, "max": 128},
            "warmup_s": 5},
        "benchmarks/chip/cells/other-8L.steady.json": {
            "slots": 8, "page_size": 16, "rate_per_s": 2.0,
            "check_requests": 4, "max_logit_gap": 1.0},
        "benchmarks/chip/metrics/slots_busy.py":
            "def read(rec):\n    return 42.0\n",
    }
    bench["configs"].append({"name": "other-8L", "source": base["source"],
                             "file": "benchmarks/chip/configs/other-8L.json",
                             "reduced": ["num_hidden_layers"], "why": "t"})
    bench["workloads"].append({"name": "other-8L.steady",
                               "config": "other-8L", "traffic": "steady",
                               "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "slots_busy", "unit": "%",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": "itl_p50_ms",
                               "workloads": ["other-8L.steady"]})
    write_tree(tmp_path, bench, files)
    c = spec.load_cell("other-8L.steady", spec.load_benchmark(tmp_path), chip)
    assert c.shape.layers == 8 and c.traffic["name"] == "steady"
    assert [m["name"] for m in c.per_layer][-1] == "slots_busy"
    assert spec.metric_reader("slots_busy", chip)(None) == 42.0
    assert all(m["name"] != "queue_wait_p95_ms" for m in c.per_layer)
