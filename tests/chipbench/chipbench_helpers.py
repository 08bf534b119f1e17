"""Shared helpers of the chip benchmark's CPU tests: the harness on the
import path, a tiny cell, and a fake engine for the serving loop."""
from __future__ import annotations

import copy
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))

TINY_CONFIG = {
    "name": "tiny", "source": "test",
    "architectures": ["Qwen3ForCausalLM"], "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "initializer_range": 0.02, "torch_dtype": "bfloat16", "qk_norm": True,
    "reduced": {}, "assumed": {}, "deployment": {}, "chips": 1}

TINY_CHAT = {
    "name": "chat", "loop": "open",
    "arrivals": {"process": "gamma", "cv": 2.0},
    "prompt": {"median": 24, "sigma": 0.9, "snap": [16, 32, 48]},
    "output": {"median": 8, "sigma": 0.7, "min": 2, "max": 16},
    "warmup_s": 0.5}

TINY_CELL = {"slots": 4, "page_size": 8, "rate_per_s": 20.0,
             "check_requests": 4, "max_logit_gap": 0.05}


def tiny_cell(config=None, traffic=None, params=None, name="tiny.chat"):
    from harness import spec
    bench = spec.load_benchmark()
    config = dict(config or TINY_CONFIG)
    return spec.Cell(
        name=name, chips=1, config=config,
        traffic=copy.deepcopy(traffic or TINY_CHAT),
        params=dict(params or TINY_CELL),
        end_to_end=tuple(bench["end_to_end"]),
        per_layer=tuple(bench["per_layer"]),
        arch=spec.architecture(config))


def arch_id(module) -> str:
    """A module of ``harness/arch/`` by its file name, for test ids."""
    return Path(module.__file__).stem


def write_tree(root: Path, bench: dict, files: dict) -> None:
    """A checkout with ``BENCHMARK.json`` and the given files."""
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for rel, text in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text if isinstance(text, str) else json.dumps(text))


@dataclasses.dataclass
class _Pend:
    rid: int
    prompt: list


@dataclasses.dataclass
class _Slot:
    rid: int
    prompt: list
    generated: list
    max_new: int

    @property
    def pos(self):
        return len(self.prompt) + len(self.generated) - 1


@dataclasses.dataclass
class _Done:
    rid: int
    tokens: list
    outcome: str = "complete"


class FakeEngine:
    """The serving surface the harness drives: ``submit``, ``step``,
    ``queue``, ``slots``, ``programs``, ``stats``, ``n_ctx``; every step admits into
    free slots and emits one token per active slot."""

    def __init__(self, slots=4, n_ctx=64):
        self.queue, self.slots = [], [None] * slots
        self.n_ctx = n_ctx
        self.programs, self.stats = {}, {"preemptions": 0,
                                         "tier_demotions": 0}
        self._rid = 0
        self.submitted = []

    def submit(self, prompt, max_new):
        self.queue.append(_Pend(self._rid, list(prompt)))
        self.submitted.append((self._rid, len(prompt), max_new))
        self._max = getattr(self, "_max", {})
        self._max[self._rid] = max_new
        self._rid += 1
        return self._rid - 1

    def _admit_one(self):
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not self.queue or not free:
            return False
        p = self.queue.pop(0)
        self.slots[free[0]] = _Slot(p.rid, p.prompt, [7], self._max[p.rid])
        return True

    def _exec(self, phase, *args):
        return None

    def step(self):
        while self._admit_one():
            pass
        self._exec("decode")
        done = []
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            if len(s.generated) < s.max_new:
                s.generated.append(len(s.generated))
            if len(s.generated) >= s.max_new:
                done.append(_Done(s.rid, list(s.generated)))
                self.slots[i] = None
        return done


def recorded(trace: str, cell):
    """A traced run's record around a trace recorded on a TPU v5e
    (``data/<trace>.xplane.pb``, ``record_trace.py``) as ``cell`` would
    read it: the three ``bench.step`` spans are the traced steps (a
    64-token prefill, then one slot decoding at contexts 65, 66, 67), and
    one request, due 10 ms before the window's first step, is admitted
    by it and served a token at the end of each."""
    from harness import peaks, serve_loop, trace_reduce
    data = Path(__file__).resolve().parent / "data"
    meta = json.loads((data / f"{trace}_hlo_meta.json").read_text())
    red = trace_reduce.reduce(str(data / f"{trace}.xplane.pb"), meta)
    spans = [s for s in red.spans if s.name == "bench.step"]
    steps = [serve_loop.Step(s.start, s.end, [64] if i == 0 else [],
                             [65 + i]) for i, s in enumerate(spans)]
    t_start = red.window[0] - 0.010
    req = serve_loop.Req(0, t_start, 64, 8)
    req.admit_step, req.times = 0, [s.end for s in spans]
    rec = serve_loop.Record(
        cell=cell, seed=0, chips=1, t_start=t_start, t_end=red.window[1],
        setup_s=1.0, requests=[req], steps=steps, counters_start={},
        counters_end={}, compiles_in_window=0, trace=red,
        traced_steps=(0, len(steps)))
    rec.peaks = peaks.for_kind("TPU v5 lite")
    return rec
