"""``run.py`` as a command: no chip, no result; and a whole run, the chip
look skipped, whose ``correct`` fails when the timed path is broken."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench_helpers import CHIP, ROOT, TINY_CONFIG, tiny_cell

from harness import faults, peaks

PEAKS = peaks.Peaks(1e12, 1e11, "test")


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def _run_cmd(cwd, seed="2147483711"):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen3-8b-4L.chat", "--seed", seed, "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=_cpu_env(), capture_output=True, text=True,
        timeout=240)


def _no_result(p):
    lines = p.stdout.strip().splitlines()
    assert p.returncode != 0
    assert not lines or not lines[-1].lstrip().startswith("{")


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = _run_cmd(ROOT)
    _no_result(p)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    has no program to serve."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cmd(tmp_path)
    _no_result(p)
    assert "No module named 'repro'" in p.stderr


@pytest.fixture(scope="module")
def run_mod():
    spec = importlib.util.spec_from_file_location("chipbench_run",
                                                  CHIP / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _execute(run_mod, cell, seed=2**31 + 3, **kw):
    import jax
    devs = jax.devices()[:1]
    return run_mod.execute(cell, seed, 1.5, False, devs, 1, PEAKS, **kw)


@pytest.mark.parametrize("scaled", [False, True], ids=["published_init",
                                                      "scaled_init"])
def test_a_sound_run_is_correct(run_mod, scaled):
    out = _execute(run_mod, tiny_cell(config=SCALED if scaled else None))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert {"output_tok_s", "itl_p50_ms", "setup_s"} <= set(out["metrics"])
    assert "readings" not in out


@pytest.mark.parametrize("fault", sorted(faults.FAULTS),
                         ids=lambda name: f"_{name}")
def test_a_broken_timed_path_makes_the_run_incorrect(run_mod, fault):
    """Each fault a served model can have on one chip, planted under the
    timed path's dispatch, makes ``correct`` false."""
    out = _execute(run_mod, tiny_cell(config=SCALED),
                   fault=faults.FAULTS[fault])
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > 0.05


def test_the_control_in_the_program_place_makes_the_run_incorrect(run_mod):
    """The float8 control, compared where the served tokens are, fails
    the run's own check at the cell's limit, while the served tokens of
    the same run pass it."""
    cell = tiny_cell(config=SCALED)
    out = _execute(run_mod, cell, control=True)
    limit = cell.params["max_logit_gap"]
    r = out["readings"]
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"] == {
        "value": r["control_max_logit_gap"], "limit": limit}
    assert r["served_max_logit_gap"] <= limit < r["control_max_logit_gap"]


# The published initializer_range 0.02 scaled by sqrt(4096 / 64), so that
# a layer's output weighs against the residual stream as it does at
# qwen3-8b's width; at 0.02 and width 64 attention barely moves the
# logits, and a cache that is never written reads about 0.05 (CPU).
SCALED = dict(TINY_CONFIG, initializer_range=0.16)
