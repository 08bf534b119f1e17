"""Every cell, configuration, mix and metric of ``BENCHMARK.json`` loads by
name, the file keeps to the benchmark's contract whatever cells it holds,
and a new cell, on one chip or four, is added with new files and entries
alone."""
import copy
import json
import shutil

import pytest

from chipbench_helpers import CHIP, ROOT, write_tree

from harness import spec

BENCH = spec.load_benchmark()
CONTRACT_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def contract_problems(bench: dict, root=ROOT, chip=CHIP) -> list:
    """What in ``bench`` breaks the contract the harness relies on: each
    cell's configuration, mix and cell file, its chips (1 or 4, as its
    configuration file says), at most half the cells (rounded down, one
    always) on four chips, a reader for every metric, metric ``workloads``
    that name only cells, every cell reporting ``setup_s`` and at least one
    more end-to-end metric, and bounds in [0.01, 0.25]."""
    out = []
    configs = {c["name"]: c for c in bench["configs"]}
    cells = [w["name"] for w in bench["workloads"]]
    if len(set(cells)) != len(cells):
        out.append("a cell is named twice")
    for w in bench["workloads"]:
        cfg = configs.get(w["config"])
        if cfg is None:
            out.append(f"{w['name']}: no configuration {w['config']}")
            continue
        chips = json.loads((root / cfg["file"]).read_text())["chips"]
        if w["chips"] not in (1, 4) or w["chips"] != chips:
            out.append(f"{w['name']}: chips {w['chips']}, its "
                       f"configuration file says {chips}")
        for part in (chip / "traffic" / f"{w['traffic']}.json",
                     chip / "cells" / f"{w['name']}.json"):
            if not part.is_file():
                out.append(f"{w['name']}: no {part.relative_to(root)}")
        reported = [m["name"] for m in bench["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        if "setup_s" not in reported or len(reported) < 2:
            out.append(f"{w['name']}: reports {reported}")
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    if four > max(1, len(cells) // 2):
        out.append(f"{four} of {len(cells)} cells ask for 4 chips")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not (chip / "metrics" / f"{m['name']}.py").is_file():
            out.append(f"{m['name']}: no reader")
        stray = set(m.get("workloads", ())) - set(cells)
        if stray:
            out.append(f"{m['name']}: names no cell {sorted(stray)}")
    for m in bench["end_to_end"]:
        if not 0.01 <= m["bound"] <= 0.25:
            out.append(f"{m['name']}: bound {m['bound']}")
    return out


def test_benchmark_has_the_contract_keys_and_cells():
    assert set(BENCH) == CONTRACT_KEYS
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["workloads"]
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    assert contract_problems(BENCH) == []


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_by_name(cell):
    c = spec.load_cell(cell)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert c.chips == entry["chips"] == c.config["chips"]
    assert c.params["slots"] > 0 and c.params["max_logit_gap"] > 0
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    if c.traffic["loop"] == "open":
        assert c.params["rate_per_s"] > 0


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader_and_names_only_cells(metric):
    assert callable(spec.metric_reader(metric["name"]))
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


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
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


def test_per_layer_metrics_name_a_reported_end_to_end_metric():
    for m in BENCH["per_layer"]:
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        for w in m.get("workloads", [x["name"] for x in BENCH["workloads"]]):
            assert "workloads" not in moved or w in moved["workloads"]


def test_the_contract_check_finds_what_breaks_it():
    bench = copy.deepcopy(BENCH)
    bench["workloads"][0]["chips"] = 2
    bench["end_to_end"][0]["bound"] = 0.5
    bench["per_layer"][0]["workloads"] = ["no-such.cell"]
    bench["end_to_end"].append({"name": "no_reader", "bound": 0.1})
    problems = contract_problems(bench)
    assert len(problems) == 4, problems


def _add_cell(tmp_path, chips: int):
    """A configuration, a mix, a metric and a cell added as new files and
    new entries to a copy of the benchmark; no existing file changes."""
    chip = tmp_path / "benchmarks" / "chip"
    for part in ("metrics", "configs", "traffic", "cells"):
        shutil.copytree(CHIP / part, chip / part)
    bench = copy.deepcopy(BENCH)
    base = json.loads((CHIP / "configs" / "qwen3-8b-4L.json").read_text())
    name = f"other-8L-c{chips}"
    files = {
        f"benchmarks/chip/configs/{name}.json": dict(
            base, name=name, num_hidden_layers=8, chips=chips),
        "benchmarks/chip/traffic/steady.json": {
            "name": "steady", "loop": "open",
            "arrivals": {"process": "gamma", "cv": 1.0},
            "prompt": {"median": 128, "sigma": 0.5, "snap": [128, 256]},
            "output": {"median": 64, "sigma": 0.5, "min": 8, "max": 128},
            "warmup_s": 5},
        f"benchmarks/chip/cells/{name}.steady.json": {
            "slots": 8, "page_size": 16, "rate_per_s": 2.0,
            "check_requests": 4, "max_logit_gap": 1.0},
        "benchmarks/chip/metrics/slots_busy.py":
            "def read(rec):\n    return 42.0\n",
    }
    bench["configs"].append({"name": name, "source": base["source"],
                             "file": f"benchmarks/chip/configs/{name}.json",
                             "reduced": ["num_hidden_layers"], "why": "t"})
    bench["workloads"].append({"name": f"{name}.steady", "config": name,
                               "traffic": "steady", "chips": chips,
                               "why": "t"})
    bench["per_layer"].append({"name": "slots_busy", "unit": "%",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": "itl_p50_ms",
                               "workloads": [f"{name}.steady"]})
    write_tree(tmp_path, bench, files)
    return spec.load_benchmark(tmp_path), chip, f"{name}.steady"


def _check_added(tmp_path, bench, chip, cell, chips):
    assert contract_problems(bench, tmp_path, chip) == []
    c = spec.load_cell(cell, bench, chip)
    assert c.chips == chips == c.config["chips"]
    assert c.shape.layers == 8 and c.traffic["name"] == "steady"
    assert [m["name"] for m in c.per_layer][-1] == "slots_busy"
    assert spec.metric_reader("slots_busy", chip)(None) == 42.0
    assert all(m["name"] != "queue_wait_p95_ms" for m in c.per_layer)
    for w in BENCH["workloads"]:
        assert spec.load_cell(w["name"], bench, chip).per_layer == \
            spec.load_cell(w["name"]).per_layer


def test_a_new_cell_is_data_only(tmp_path):
    """A configuration, a mix, a metric and a cell added as new files
    and new entries; no existing file changes."""
    bench, chip, cell = _add_cell(tmp_path, chips=1)
    _check_added(tmp_path, bench, chip, cell, 1)


def test_a_new_four_chip_cell_is_data_only(tmp_path):
    """The same with a configuration whose file says ``"chips": 4``: it
    loads through ``spec.load_cell`` and keeps to the contract, as long
    as no more than half the cells (one always) ask for four chips."""
    bench, chip, cell = _add_cell(tmp_path, chips=4)
    _check_added(tmp_path, bench, chip, cell, 4)
    twin = dict(next(w for w in bench["workloads"] if w["name"] == cell),
                name=f"{cell}-twin")
    shutil.copy(chip / "cells" / f"{cell}.json",
                chip / "cells" / f"{twin['name']}.json")
    bench["workloads"].append(twin)
    bench["workloads"] = [w for w in bench["workloads"] if w["chips"] == 4]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"]
                              if any(x["name"] == w
                                     for x in bench["workloads"])]
    assert len(bench["workloads"]) >= 2
    assert contract_problems(bench, tmp_path, chip) == [
        f"{len(bench['workloads'])} of {len(bench['workloads'])} cells "
        f"ask for 4 chips"]


def test_the_held_back_four_chip_cell_needs_entries_alone():
    """``qwen3-8b-tp4.chat`` keeps its configuration, cell and reader
    files; entries in ``BENCHMARK.json`` alone put it back."""
    bench = copy.deepcopy(BENCH)
    cell = "qwen3-8b-tp4.chat"
    base = next(c for c in BENCH["configs"] if c["name"] == "qwen3-8b-4L")
    bench["configs"].append(dict(
        base, name="qwen3-8b-tp4", reduced=[],
        file="benchmarks/chip/configs/qwen3-8b-tp4.json"))
    bench["workloads"].append({"name": cell, "config": "qwen3-8b-tp4",
                               "traffic": "chat", "chips": 4, "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen3-8b-4L.chat" in m.get("workloads", ()):
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "collective_exposed_ms_per_step", "unit": "ms",
        "better": "lower", "source": "device_trace", "layer": "collectives",
        "moves": "itl_p50_ms", "workloads": [cell]})
    assert contract_problems(bench) == []
    c = spec.load_cell(cell, bench)
    assert c.chips == c.config["chips"] == 4 and c.shape.layers == 36
    assert c.config["reduced"] == {} and c.params["slots"] == 32
    assert c.params["rate_per_s"] == 1.76
    assert [m["name"] for m in c.per_layer][-1] == \
        "collective_exposed_ms_per_step"
