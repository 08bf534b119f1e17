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
    timed path's dispatch, makes ``correct`` false; the exchange between
    chips, which one chip does not have, is refused there (the four-chip
    run below leaves it out)."""
    if faults.FAULTS[fault] in faults.ON_PARAMS:
        with pytest.raises(ValueError, match="no exchange"):
            _execute(run_mod, tiny_cell(config=SCALED),
                     fault=faults.FAULTS[fault])
        return
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


MESH_RUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, importlib.util, json, sys
sys.path.insert(0, sys.argv[1])
import jax
from chipbench_helpers import CHIP, TINY_CONFIG, tiny_cell
from harness import faults, peaks
spec = importlib.util.spec_from_file_location("chipbench_run", CHIP / "run.py")
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
cfg = dict(TINY_CONFIG, num_attention_heads=8, num_key_value_heads=4,
           hidden_size=128, initializer_range=0.16, chips=4)
cell = dataclasses.replace(tiny_cell(config=cfg), chips=4)
out = {}
for name in ("sound", "exchange_left_out"):
    r = run.execute(cell, 2**31 + 5, 1.5, False, jax.devices()[:4], 4,
                    peaks.Peaks(1e12, 1e11, "t"),
                    fault=faults.FAULTS.get(name))
    out[name] = [r["correct"], r["checks"]["max_logit_gap"]["value"],
                 r["failed"], r["attempted"]]
print("RESULT " + json.dumps(out))
"""


def test_a_four_chip_run_is_correct_until_the_exchange_is_left_out():
    """A whole run of a four-chip cell on four forced host devices, the
    chip look skipped: the engine serves tensor-parallel over the mesh
    and the run is correct; with the exchange between chips left out it
    is not."""
    p = subprocess.run([sys.executable, "-c", MESH_RUN,
                        str(ROOT / "tests" / "chipbench")],
                       env=_cpu_env(), capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(next(ln for ln in p.stdout.splitlines()
                          if ln.startswith("RESULT "))[len("RESULT "):])
    correct, gap, failed, attempted = out["sound"]
    assert correct and failed == 0 and attempted > 0, out
    correct, gap, _, _ = out["exchange_left_out"]
    assert not correct and gap > 0.05, out
    assert "KV pool after warm-up" in p.stderr
    assert "decode regime paged-" in p.stderr
