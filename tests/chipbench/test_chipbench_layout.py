"""Every cell, configuration, mix and metric of ``BENCHMARK.json`` loads by
name, the file keeps to the benchmark's contract whatever cells it holds,
and a new cell, on one chip or four, or a new architecture, is added with
new files and entries alone."""
import copy
import dataclasses
import hashlib
import json
import shutil
from pathlib import Path

import jax
import pytest

from chipbench_helpers import CHIP, ROOT, recorded, write_tree

from harness import serve_loop, spec, weights as W

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
    for part in ("metrics", "configs", "traffic", "cells", "harness/arch"):
        shutil.copytree(CHIP / part, chip / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
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
    assert c.config["num_hidden_layers"] == 8
    assert c.traffic["name"] == "steady"
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
    assert c.chips == c.config["chips"] == 4
    assert c.config["num_hidden_layers"] == 36
    assert c.config["reduced"] == {} and c.params["slots"] == 32
    assert c.params["rate_per_s"] == 1.76
    assert [m["name"] for m in c.per_layer][-1] == \
        "collective_exposed_ms_per_step"


def _files(root: Path) -> dict:
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


# The new module: the dense decoder's arithmetic under another name,
# whose model FLOPs count twice, so that ``step_mfu`` shows whose costs
# it read.
TWICE = """

_decode, _prefill = decode_model_flops, prefill_model_flops


def decode_model_flops(config, ctx_lens):
    return 2.0 * _decode(config, ctx_lens)


def prefill_model_flops(config, prompt_len):
    return 2.0 * _prefill(config, prompt_len)
"""


def test_a_new_architecture_is_data_only(tmp_path):
    """An architecture module, a configuration that names it, a cell and
    entries added to a copy of the benchmark: the cell loads with the new
    module, which builds the program's ``ModelConfig``, draws weights in
    the program's layout and prices a recorded trace's ``step_mfu``; no
    file of the benchmark changes."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(chip)
    module = (CHIP / "harness" / "arch" / "dense_gqa.py").read_text()
    module = module.replace(
        'ARCHITECTURES = ("Qwen3ForCausalLM", "MistralForCausalLM")',
        'ARCHITECTURES = ("ToyDecoderForCausalLM",)').replace(
        '["Qwen3ForCausalLM"]', '["ToyDecoderForCausalLM"]') + TWICE
    base = json.loads((CHIP / "configs" / "qwen3-8b-4L.json").read_text())
    name, cell = "toy-4L", "toy-4L.chat"
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({"name": name, "source": base["source"],
                             "file": f"benchmarks/chip/configs/{name}.json",
                             "reduced": ["num_hidden_layers"], "why": "t"})
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": "chat", "chips": 1, "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen3-8b-4L.chat" in m.get("workloads", ()):
            m["workloads"].append(cell)
    write_tree(tmp_path, bench, {
        "benchmarks/chip/harness/arch/toy_decoder.py": module,
        f"benchmarks/chip/configs/{name}.json": dict(
            base, name=name, architectures=["ToyDecoderForCausalLM"]),
        f"benchmarks/chip/cells/{cell}.json":
            (CHIP / "cells" / "qwen3-8b-4L.chat.json").read_text()})
    after = _files(chip)
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        Path(f"cells/{cell}.json"), Path(f"configs/{name}.json"),
        Path("harness/arch/toy_decoder.py")]

    bench = spec.load_benchmark(tmp_path)
    assert contract_problems(bench, tmp_path, chip) == []
    c = spec.load_cell(cell, bench, chip)
    arch = c.arch
    assert Path(arch.__file__) == chip / "harness" / "arch" / "toy_decoder.py"
    assert arch.ARCHITECTURES == ("ToyDecoderForCausalLM",)
    cfg = arch.model_config(c.config)
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff) == (4, 4096, 12288)
    from repro.models.lm import LM
    small = dataclasses.replace(c, config=arch.SMOKE)
    _, program, _ = serve_loop.make_weights(small, 3)
    W.check_layout(jax.eval_shape(program, W.seed_key(0)),
                   LM(arch.model_config(arch.SMOKE)).abstract_params())
    params = jax.jit(program)(W.seed_key(2**31 + 3))
    assert all(x.size for x in jax.tree.leaves(params))
    mfu = spec.metric_reader("step_mfu", chip)
    qwen = spec.load_cell("qwen3-8b-4L.chat", bench, chip)
    assert spec.architecture(qwen.config, chip) is not arch
    twice, once = mfu(recorded("decode", c)), mfu(recorded("decode", qwen))
    assert once > 0 and twice == pytest.approx(2 * once, rel=1e-12)


def test_an_architecture_no_module_or_two_modules_claim_raises(tmp_path):
    """A configuration's ``architectures[0]`` must be claimed by exactly
    one module of ``harness/arch/``; the error names the value."""
    chip = tmp_path / "chip"
    shutil.copytree(CHIP / "harness" / "arch", chip / "harness" / "arch",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((CHIP / "configs" / "qwen3-8b-4L.json").read_text())
    assert spec.architecture(cfg, chip).ARCHITECTURES[0] == "Qwen3ForCausalLM"
    with pytest.raises(LookupError, match="'MellumForCausalLM'.* 0 modules"):
        spec.architecture(dict(cfg, architectures=["MellumForCausalLM"]),
                          chip)
    with pytest.raises(LookupError, match="None.* 0 modules"):
        spec.architecture({k: v for k, v in cfg.items()
                           if k != "architectures"}, chip)
    shutil.copy(chip / "harness" / "arch" / "dense_gqa.py",
                chip / "harness" / "arch" / "dense_gqa_twin.py")
    with pytest.raises(LookupError,
                       match="'Qwen3ForCausalLM'.* 2 modules .*dense_gqa_twin"):
        spec.architecture(cfg, chip)
