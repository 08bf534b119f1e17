"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``): qwen3-8b-4L at 2 slots, one admission with its
prefill, then two decode steps, each inside a ``bench.step`` span."""
import json
from pathlib import Path

import pytest

from chipbench_helpers import ROOT  # noqa: F401  (harness on the path)

from harness import reading, trace_reduce

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def red():
    with open(DATA / "decode_hlo_meta.json") as f:
        meta = json.load(f)
    return trace_reduce.reduce(str(DATA / "decode.xplane.pb"), meta)


def test_planes_programs_and_spans(red):
    assert red.devices == [0]
    names = [m[3] for m in red.modules]
    assert names.count(reading.DECODE) == 3
    assert names.count(reading.PREFILL) == 1
    steps = [s for s in red.spans if s.name == "bench.step"]
    assert len(steps) == 3
    assert red.window == (steps[0].start, max(s.end for s in steps))


def test_every_op_lies_in_a_program_and_self_times_add_up(red):
    for m in (reading.DECODE, reading.PREFILL):
        total = sum(b - a for d, a, b, n in red.modules if n == m)
        ops = [o for o in red.ops if o.module == m]
        assert ops
        # nested ops (a while and its body) count once: self times sum to
        # at most the programs' device time
        assert 0.9 * total < sum(o.self_s for o in ops) <= total * 1.0001
    busy = trace_reduce.busy_s(red, 0)
    assert 0 < busy <= red.window_s


def test_kernels_found_by_jit_path_in_their_own_program(red):
    attn = [o for o in red.ops if reading.PAGED_ATTENTION in o.path]
    mlp = [o for o in red.ops if reading.MLP_CHAIN in o.path]
    assert attn and mlp
    assert {o.module for o in attn} == {reading.DECODE}
    assert any(o.instr.startswith("fused_attention_partial") for o in attn)
    # a jit path names the program that ran the op, never another
    for o in red.ops:
        if o.path.startswith("jit("):
            prog = o.module.removeprefix("jit_")
            assert o.path.startswith(f"jit({prog})"), (o.module, o.path)
    assert reading.kernel_time(red_rec(red), reading.PAGED_ATTENTION,
                               reading.DECODE) > 0


def test_idle_gaps_name_the_span_they_fall_in(red):
    gaps = trace_reduce.idle_gaps(red, 0)
    assert gaps and gaps == sorted(gaps, reverse=True)
    assert {name for _, name in gaps} <= {"bench.step", "none"}
    idle = sum(s for s, _ in gaps)
    assert idle == pytest.approx(red.window_s - trace_reduce.busy_s(red, 0))


def red_rec(red):
    class Rec:
        trace = red
    return Rec()
