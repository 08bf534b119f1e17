"""The serving engine's own host spans in a traced run, and the device's
idle time split by them.

The engine opens a ``jax.profiler`` span around each scheduler iteration
(``engine.step``) and, inside it, one around each part of the host's
work: ``engine.schedule``, ``engine.admit`` (one per admitted request,
its prefill's dispatch inside), ``engine.inputs``, ``engine.dispatch``,
``engine.sample`` and ``engine.commit`` (docs/serving.md).  They share
the device trace's clock.  ``trace_reduce.reduce`` keeps the harness's
``bench.*`` spans alone; ``attach`` adds the engine's, with their stats,
and leaves the window (first to last ``bench.*`` span) as it was, so
every reading taken from the reduction without them reads the same.

A trace of a program that opens no such span gives nothing to split:
``split`` then returns None.
"""
from __future__ import annotations

import bisect
import dataclasses

from . import reading, trace_reduce

PREFIX = "engine."
STEP = "engine.step"


@dataclasses.dataclass
class Span(trace_reduce.Span):
    stats: dict = dataclasses.field(default_factory=dict)


def _order(spans) -> list:
    """By start; a span before the spans nested in it."""
    return sorted(spans, key=lambda s: (s.start, -s.end))


def read_spans(path: str) -> list:
    """Every host span of the trace at ``path`` whose name starts with
    ``engine.``, with its stats."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = e.start_ns * 1e-9
                    out.append(Span(s, s + e.duration_ns * 1e-9, e.name,
                                    {k: v for k, v in e.stats
                                     if k != "_r"}))
    return _order(out)


def attach(red: trace_reduce.Reduced, path: str) -> trace_reduce.Reduced:
    """``red`` with the engine's spans of the trace at ``path`` added."""
    return dataclasses.replace(red, spans=_order(red.spans
                                                 + read_spans(path)))


def _steps_and_children(red):
    """The ``engine.step`` spans, and for each the engine spans directly
    inside it (a prefill's ``engine.dispatch`` inside its
    ``engine.admit`` is not one of them)."""
    spans = _order(red.spans)
    steps = [s for s in spans if s.name == STEP]
    starts = [s.start for s in steps]
    children = [[] for _ in steps]
    for c in spans:
        if not c.name.startswith(PREFIX) or c.name == STEP:
            continue
        i = bisect.bisect_right(starts, c.start) - 1
        if i < 0 or c.end > steps[i].end:
            continue
        kids = children[i]
        if not kids or c.start >= kids[-1].end:
            kids.append(c)
    return steps, starts, children


def segments(red: trace_reduce.Reduced) -> list:
    """[(start, end, name)] cutting the window into the pieces that
    ``idle_by_phase`` gives to one name each: inside an ``engine.step``,
    the child span that covers the instant, else ``engine.step``;
    outside every step, the innermost ``bench.*`` span, else ``none``."""
    lo, hi = red.window
    steps, starts, children = _steps_and_children(red)
    bench = [s for s in red.spans if s.name.startswith("bench.")]

    def name_at(t):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < steps[i].end:
            return next((c.name for c in children[i]
                         if c.start <= t < c.end), STEP)
        inner = None
        for s in bench:
            if s.start <= t < s.end and (inner is None
                                         or s.start >= inner.start):
                inner = s
        return inner.name if inner is not None else "none"

    cuts = sorted({lo, hi} | {t for s in red.spans for t in (s.start, s.end)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        name = name_at((a + b) / 2)
        if out and out[-1][2] == name:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_by_phase(red: trace_reduce.Reduced, device: int) -> dict:
    """{name: seconds} of the device's idle time inside the window, each
    instant given to one name as ``segments`` says; the values sum to
    the window less the device's busy time."""
    lo, hi = red.window
    idle = trace_reduce.gaps([(o.start, o.end) for o in red.ops
                              if o.device == device], lo, hi)
    segs = segments(red)
    out: dict = {}
    j = 0
    for a, b in idle:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s, e, name = segs[k]
            out[name] = out.get(name, 0.0) + min(b, e) - max(a, s)
            k += 1
    return out


def _in_window(red, name: str) -> list:
    lo, hi = red.window
    return [s for s in red.spans if s.name == name and lo <= s.start < hi]


def _mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def split(red: trace_reduce.Reduced) -> dict | None:
    """The device's idle time by engine phase, averaged over the chips:
    milliseconds per decode step for every name ``idle_by_phase`` gives,
    ``engine.admit``'s per admitted request as well, and the share of the
    idle inside ``engine.step`` spans that no child span covers; beside
    them what the spans' stats say of the work: each admission's request,
    prompt and padded lengths and device idle, the active slots and
    summed contexts of a decode step, and the dispatches by phase.  Spans
    count when they start inside the window.  None when the trace holds
    no ``engine.step``."""
    steps = _in_window(red, STEP)
    if not steps or not red.devices:
        return None
    n_dev = len(red.devices)
    lo, hi = red.window
    n_decode = sum(1 for m in red.modules if m[3] == reading.DECODE
                   and lo <= m[1] <= hi) // n_dev
    admits = _in_window(red, "engine.admit")
    inputs = _in_window(red, "engine.inputs")
    busy_by_dev = {d: [(o.start, o.end) for o in red.ops if o.device == d]
                   for d in red.devices}
    idle: dict = {}
    for d in red.devices:
        for k, v in idle_by_phase(red, d).items():
            idle[k] = idle.get(k, 0.0) + v / n_dev
    inside = sum(v for k, v in idle.items() if k.startswith(PREFIX))
    busy = sum(trace_reduce.busy_s(red, d) for d in red.devices) / n_dev
    dispatches: dict = {}
    for s in _in_window(red, "engine.dispatch"):
        phase = s.stats.get("phase", "")
        dispatches[phase] = dispatches.get(phase, 0) + 1
    return {
        "decode_steps": n_decode, "engine_steps": len(steps),
        "admits": len(admits),
        "idle_ms_per_decode_step": {
            k: 1e3 * v / max(1, n_decode) for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])},
        "admit_idle_ms_per_prefill": (
            1e3 * idle.get("engine.admit", 0.0) / len(admits)
            if admits else None),
        "step_self_share": (idle.get(STEP, 0.0) / inside if inside
                            else None),
        "unsplit_s": red.window_s - busy - sum(idle.values()),
        "prefills": [
            {"rid": a.stats.get("rid"), "tokens": a.stats.get("tokens"),
             "padded": a.stats.get("padded"),
             "idle_ms": 1e3 * sum(
                 a.end - a.start - trace_reduce.union_length(
                     b, a.start, a.end) for b in busy_by_dev.values())
             / n_dev}
            for a in admits],
        "active_per_decode_step": _mean(
            s.stats["active"] for s in inputs if "active" in s.stats),
        "ctx_tokens_per_decode_step": _mean(
            s.stats["ctx_tokens"] for s in inputs
            if "ctx_tokens" in s.stats),
        "dispatches": dispatches,
    }
