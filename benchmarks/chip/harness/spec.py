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
* ``metrics/<metric>.py``     a reader ``read(record)`` per metric;
* ``harness/arch/<name>.py``  an architecture: the module whose
  ``ARCHITECTURES`` holds the configuration's own ``architectures[0]``
  gives the program's configuration, the weight draw and its layout,
  the reference's forward pass and the costs the readers charge.

An architecture module exposes ``ARCHITECTURES``, ``SMOKE`` (a tiny
configuration in HF keys, for the CPU tests), ``model_config(config)``,
``leaf_shapes(config)``, ``canonical(config, key, dtype, init_sd)``,
``to_program(canon, config)``, ``spread(name, leaf, n)``,
``hidden(w, tokens, config, control)``, ``decode_model_flops(config,
ctx_lens)``, ``prefill_model_flops(config, prompt_len)``, and for each
kernel it has a roofline reader for, that kernel's ``(flops, bytes)``
function (``paged_attention(config, ctx_lens)``, ``mlp_chain(config,
rows)``).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import importlib.util
import json
import sys
from pathlib import Path

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
    arch: object               # the module of harness/arch/ serving it

    @property
    def max_prompt(self) -> int:
        return max(self.traffic["prompt"]["snap"])

    @property
    def max_output(self) -> int:
        return int(self.traffic["output"]["max"])


@functools.cache
def _load(path: Path):
    """A module loaded from its file, once per process, so that the same
    file is the same module (a jit static argument) wherever it is
    found.  It is registered in ``sys.modules`` under a name made from
    its path, as its dataclasses need, so that two checkouts' modules of
    one file name stay apart."""
    name = (f"chipbench_arch_{path.stem}_"
            f"{hashlib.sha256(str(path).encode()).hexdigest()[:12]}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def arch_modules(chip_dir: Path = CHIP) -> list:
    """Every module of ``harness/arch/``, by file name."""
    return [_load(p.resolve())
            for p in sorted((chip_dir / "harness" / "arch").glob("*.py"))]


def architecture(config: dict, chip_dir: Path = CHIP):
    """The one module of ``harness/arch/`` whose ``ARCHITECTURES`` holds
    the configuration's ``architectures[0]``."""
    name = (config.get("architectures") or [None])[0]
    claims = [m for m in arch_modules(chip_dir)
              if name in m.ARCHITECTURES]
    if len(claims) != 1:
        files = [Path(m.__file__).name for m in claims]
        raise LookupError(
            f"configuration {config.get('name')!r}: architecture {name!r} "
            f"is claimed by {len(claims)} modules of harness/arch/ "
            f"{files}; exactly one must claim it")
    return claims[0]


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
                        if _reports(m, name)),
        arch=architecture(config, chip_dir))


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
