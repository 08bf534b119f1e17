"""The serving engine's own spans: recorded on the CPU around a tiny
model, and the device's idle time split by them (``engine_spans``), on a
trace built by hand and on the traces recorded on a TPU v5e
(``record_trace.py``)."""
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench_helpers import ROOT  # noqa: F401  (harness on the path)

from harness import (engine_spans, peaks, reading, serve_loop, spec,
                     trace_reduce)

DATA = Path(__file__).resolve().parent / "data"
# ``decode_spans.*``: the same three steps recorded (``record_trace.py
# --out``, files renamed) by an engine that writes its spans;
# ``decode.*`` comes from one that did not
TRACES = ["decode.xplane.pb", "decode_spans.xplane.pb"]
CHILDREN = {"engine.schedule", "engine.admit", "engine.inputs",
            "engine.dispatch", "engine.sample", "engine.commit"}


def _inside(a, b):
    return b.start <= a.start and a.end <= b.end


def test_each_step_is_one_span_with_its_phases_inside(tmp_path):
    from repro.configs import get_config
    from repro.models.lm import LM
    from repro.reliability import faults
    from repro.serving import ServingEngine
    model = LM(get_config("qwen3_8b", smoke=True))
    eng = ServingEngine(model, model.init_params(jax.random.PRNGKey(0)),
                        max_batch=2, page_size=4, n_pages=16,
                        max_pages_per_seq=6, choose_regime=False)
    prompt = np.arange(3, 9, dtype=np.int32)
    eng.run([(prompt, 2)])                      # compile outside the trace
    eng.submit(prompt, 5)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):                      # admit + decode, decode x2
            eng.step()
        # a second request whose pages are denied: requeued, no admit span
        eng.submit(prompt, 2)
        with faults.injected("page_exhaustion",
                             trigger=lambda ctx: ctx["n"] > 1):
            eng.step()
    assert eng.stats["admit_requeues"] == 1 and len(eng.queue) == 1
    spans = engine_spans.read_spans(trace_reduce.latest_xplane(
        str(tmp_path)))
    steps = [s for s in spans if s.name == "engine.step"]
    assert [s.stats["step_num"] for s in steps] == [
        eng.step_no - 3, eng.step_no - 2, eng.step_no - 1, eng.step_no]
    kids = [s for s in spans if s.name != "engine.step"]
    assert {s.name for s in kids} == CHILDREN
    per_step = [[k for k in kids if _inside(k, s)] for s in steps]
    assert sum(map(len, per_step)) == len(kids)
    for i, ks in enumerate(per_step):
        names = [k.name for k in ks]
        assert names.count("engine.schedule") == 2
        for n in ("engine.inputs", "engine.sample", "engine.commit"):
            assert names.count(n) == 1
        admits = [k for k in ks if k.name == "engine.admit"]
        assert len(admits) == (1 if i == 0 else 0)
        dispatch = [k.stats["phase"] for k in ks
                    if k.name == "engine.dispatch"]
        assert dispatch == (["prefill", "decode"] if i == 0 else ["decode"])
        inputs = next(k for k in ks if k.name == "engine.inputs")
        assert inputs.stats == {"active": 1,
                                "ctx_tokens": len(prompt) + 1 + i}
    (admit,) = [k for k in per_step[0] if k.name == "engine.admit"]
    assert admit.stats == {"rid": 1, "tokens": 6, "padded": 8}
    # the phases cover the step but for the statements between them
    covered = sum(trace_reduce.union_length([(k.start, k.end) for k in ks])
                  for ks in per_step)
    assert covered > 0.75 * sum(s.end - s.start for s in steps)


def _synthetic():
    """Bench spans over [0, 10); an engine step [1, 5.5) that admits (a
    prefill dispatch inside its ``engine.admit``) and decodes, and a step
    [7.5, 9.5); device busy [-1, 0.5), [2.2, 2.8) (prefill), [3.8, 4.6)
    and [8, 9.2) (decodes)."""
    S, Op = engine_spans.Span, trace_reduce.Op
    spans = [S(0.0, 6.0, "bench.step"), S(6.0, 6.5, "bench.wait"),
             S(6.1, 6.2, "bench.generate"), S(7.0, 10.0, "bench.step"),
             S(1.0, 5.5, "engine.step"), S(1.0, 1.5, "engine.schedule"),
             S(1.5, 3.0, "engine.admit", {"rid": 7, "tokens": 50,
                                          "padded": 64}),
             S(2.0, 2.5, "engine.dispatch", {"phase": "prefill"}),
             S(3.0, 3.2, "engine.schedule"),
             S(3.2, 3.6, "engine.inputs", {"active": 3, "ctx_tokens": 90}),
             S(3.6, 4.0, "engine.dispatch", {"phase": "decode"}),
             S(4.0, 5.0, "engine.sample"),
             S(5.0, 5.4, "engine.commit"), S(7.5, 9.5, "engine.step"),
             S(7.5, 8.5, "engine.inputs", {"active": 4, "ctx_tokens": 94}),
             S(8.5, 9.0, "engine.sample"),
             # outside the window: counted nowhere
             S(10.5, 11.0, "engine.admit", {"rid": 8, "tokens": 9,
                                            "padded": 16})]
    busy = [(-1.0, 0.5, "jit_other"), (2.2, 2.8, reading.PREFILL),
            (3.8, 4.6, reading.DECODE), (8.0, 9.2, reading.DECODE)]
    ops = [Op(0, a, b, b - a, "fusion.1", "", m) for a, b, m in busy]
    mods = [(0, a, b, m) for a, b, m in busy]
    return trace_reduce.Reduced([0], mods, ops, spans, (0.0, 10.0))


def test_idle_goes_to_the_phase_of_the_step_or_the_innermost_bench_span():
    red = _synthetic()
    idle = engine_spans.idle_by_phase(red, 0)
    want = {"bench.step": 2.0, "engine.schedule": 0.7,
            "engine.admit": 0.9, "engine.inputs": 0.9,
            "engine.dispatch": 0.2, "engine.sample": 0.4,
            "engine.commit": 0.4, "engine.step": 0.4, "bench.wait": 0.4,
            "bench.generate": 0.1, "none": 0.5}
    assert idle == pytest.approx(want, abs=1e-12)
    assert sum(idle.values()) == pytest.approx(
        red.window_s - trace_reduce.busy_s(red, 0), abs=1e-12)
    out = engine_spans.split(red)
    assert (out["decode_steps"], out["engine_steps"], out["admits"]) == (
        2, 2, 1)
    per = out["idle_ms_per_decode_step"]
    assert per["engine.sample"] == pytest.approx(200.0)
    assert per["engine.dispatch"] == pytest.approx(100.0)
    assert out["admit_idle_ms_per_prefill"] == pytest.approx(900.0)
    assert out["step_self_share"] == pytest.approx(0.4 / 3.9)
    assert out["unsplit_s"] == pytest.approx(0.0, abs=1e-12)
    (pre,) = out["prefills"]
    assert pre == {"rid": 7, "tokens": 50, "padded": 64,
                   "idle_ms": pytest.approx(900.0)}
    assert out["active_per_decode_step"] == pytest.approx(3.5)
    assert out["ctx_tokens_per_decode_step"] == pytest.approx(92.0)
    assert out["dispatches"] == {"prefill": 1, "decode": 1}


def test_no_engine_spans_nothing_to_split():
    red = _synthetic()
    red.spans = [s for s in red.spans if s.name.startswith("bench.")]
    assert engine_spans.split(red) is None
    # all idle then falls to the harness's spans, as ``idle_gaps`` names it
    idle = engine_spans.idle_by_phase(red, 0)
    assert set(idle) == {"bench.step", "bench.wait", "bench.generate",
                         "none"}


@pytest.fixture(scope="module", params=TRACES)
def recorded(request):
    """(plain reduction, reduction with the engine's spans attached) of
    a recorded chip trace."""
    import json
    path = str(DATA / request.param)
    with open(path.replace(".xplane.pb", "_hlo_meta.json")) as f:
        meta = json.load(f)
    red = trace_reduce.reduce(path, meta)
    return red, engine_spans.attach(red, path)


def test_split_sums_to_the_traced_idle(recorded):
    red, full = recorded
    assert full.window == red.window
    idle = engine_spans.idle_by_phase(full, 0)
    assert sum(idle.values()) == pytest.approx(
        red.window_s - trace_reduce.busy_s(red, 0), abs=1e-9)
    out = engine_spans.split(full)
    if not any(s.name.startswith("engine.") for s in full.spans):
        assert out is None
        return
    assert out["engine_steps"] == 3 and out["admits"] == 1
    assert abs(out["unsplit_s"]) < 1e-9
    assert out["step_self_share"] <= 0.10
    gaps = trace_reduce.idle_gaps(full, 0)
    assert gaps[0][1].startswith("engine.")


def _record(red):
    """The traced steps of ``record_trace.py``: one 64-token admission,
    then decode steps at 2 slots, one active."""
    steps = [s for s in red.spans if s.name == "bench.step"]
    rec = serve_loop.Record(
        cell=spec.load_cell("qwen3-8b-4L.chat"), seed=0, chips=1,
        t_start=red.window[0], t_end=red.window[1], setup_s=0.0,
        requests=[], counters_start={}, counters_end={},
        compiles_in_window=0, trace=red, traced_steps=(0, len(steps)),
        steps=[serve_loop.Step(s.start, s.end, [64] if i == 0 else [],
                               [65 + i]) for i, s in enumerate(steps)])
    rec.peaks = peaks.for_kind("TPU v5 lite")
    return rec


def test_every_accepted_reader_reads_the_same_with_engine_spans(recorded):
    """Engine spans nest in ``bench.step`` and leave the window as it
    was: a reduction that keeps them moves no accepted metric."""
    red, full = recorded
    reported = {m["name"]
                for m in spec.load_cell("qwen3-8b-4L.chat").per_layer}
    for m in spec.load_benchmark()["per_layer"]:
        read = spec.metric_reader(m["name"])
        plain, both = read(_record(red)), read(_record(full))
        assert plain == both, m["name"]
        if m["source"] == "device_trace" and m["name"] in reported:
            assert plain is not None, m["name"]
