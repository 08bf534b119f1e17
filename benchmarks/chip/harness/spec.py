"""Everything a cell is made of, found by the names in ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` is one entry of ``workloads``.  Its parts
are files of their own, so adding a cell, a configuration, a traffic
mix or a metric means adding files and entries, never editing one:

* ``configs/<config>.json``   the model's sizes as served (HF keys),
  its source, what was cut (``reduced``) and assumed, the deployment
  it stands for, and ``chips``;
* ``traffic/<traffic>.json``  loop kind, arrivals, length laws;
* ``cells/<cell>.json``       slots, page size, fixed rate, and the
  limit of the correctness comparison;
* ``metrics/<metric>.py``     a reader ``read(record)`` per metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

from . import costs

CHIP = Path(__file__).resolve().parents[1]       # benchmarks/chip
ROOT = CHIP.parents[1]                           # the checkout


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    params: dict
    end_to_end: tuple          # metric entries of BENCHMARK.json
    per_layer: tuple

    @property
    def shape(self) -> costs.Shape:
        return shape_of(self.config)

    @property
    def max_prompt(self) -> int:
        return max(self.traffic["prompt"]["snap"])

    @property
    def max_output(self) -> int:
        return int(self.traffic["output"]["max"])


def shape_of(config: dict) -> costs.Shape:
    c = config
    return costs.Shape(
        layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"])


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None,
              chip_dir: Path = CHIP) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = _load_json(chip_dir.parents[1] / cfg_entry["file"])
    traffic = _load_json(chip_dir / "traffic" / f"{entry['traffic']}.json")
    params = _load_json(chip_dir / "cells" / f"{name}.json")
    return Cell(
        name=name, chips=int(entry["chips"]), config=config,
        traffic=traffic, params=params,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _reports(m, name)))


def metric_reader(name: str, chip_dir: Path = CHIP):
    """The ``read(record)`` function of ``metrics/<name>.py``."""
    path = chip_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
