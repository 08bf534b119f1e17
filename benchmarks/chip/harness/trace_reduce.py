"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU the trace holds, per chip, a plane ``/device:TPU:<n>`` with the
lines ``XLA Modules`` (one event per program execution, named
``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per HLO
instruction executed, named by the instruction's text, nested: a
``while`` spans the ops of its body).  Host planes hold the harness's
own ``TraceAnnotation`` spans (``bench.*``).  Device and host events
share one clock.

Ops carry no source path in the trace, so each op is given the
``op_name`` metadata of its instruction in the compiled program that ran
it, taken from that program's optimised HLO text (``ops_metadata``).
Instruction names repeat across programs (the prefill and decode
programs both hold a ``constant_dynamic-slice_fusion``), so the lookup
is by program first.  A kernel is
found by its jit path there: ``jit(fused_attention_paged)`` covers the
page gather and the attention kernel, ``jit(fused_mlp_chain)`` the
fused MLP.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_EVENT_INSTR = re.compile(r"^%([\w.\-]+) = ")


@dataclasses.dataclass
class Op:
    device: int
    start: float          # seconds on the trace's clock
    end: float
    self_s: float         # duration minus the ops nested inside it
    instr: str            # HLO instruction name, e.g. "fusion.57"
    text: str             # the event's name: the instruction text
    module: str           # "jit_decode_step_paged", ...
    path: str = ""        # op_name metadata (jit path), "" if unknown


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str


@dataclasses.dataclass
class Reduced:
    devices: list
    modules: list         # (device, start, end, name)
    ops: list             # Op
    spans: list           # Span (host, bench.*)
    window: tuple         # (start, end): first to last harness span

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def module_base(name: str) -> str:
    return name.split("(", 1)[0]


def instr_kind(instr: str) -> str:
    """``fusion.57`` -> ``fusion``; ``fused_attention_partial.11`` ->
    ``fused_attention_partial``."""
    return re.sub(r"\.\d+$", "", instr)


COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?$")
_OPCODE = re.compile(r"^%[\w.\-]+ = .*? ([a-z][\w\-]*)\(")


def collective_kind(op: "Op") -> str | None:
    """The collective an op runs (``all-reduce``, ``all-gather``,
    ``reduce-scatter``, ``collective-permute``, ``all-to-all``, each
    also as its async ``-start`` / ``-done`` half), from its instruction
    name or, where the name was changed, the opcode in its text; None
    for any other op."""
    for kind in (instr_kind(op.instr),
                 (_OPCODE.match(op.text) or [None, ""])[1]):
        m = COLLECTIVE.match(kind)
        if m:
            return m.group(0)
    return None


def exposed_collective_s(red: "Reduced", device: int,
                         module: str | None = None) -> tuple[float, int]:
    """(seconds, ops) of the device's collective ops that start inside
    the window (in ``module`` alone where given), less the time in which
    a compute op of the same device overlaps them.  Compute ops are the
    other ops that hold no op nested inside them: the ``while`` of a
    layer scan, which encloses its collectives, hides none of them."""
    lo, hi = red.window
    ops = [o for o in red.ops if o.device == device]
    merged: list = []
    for s, e in sorted((o.start, o.end) for o in ops
                       if not collective_kind(o)
                       and o.self_s >= (o.end - o.start) * (1 - 1e-9)):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    starts = [m[0] for m in merged]
    exposed, n = 0.0, 0
    for o in ops:
        if (not collective_kind(o) or not lo <= o.start <= hi
                or module is not None and o.module != module):
            continue
        n += 1
        hidden = 0.0
        i = max(0, bisect.bisect_right(starts, o.start) - 1)
        while i < len(merged) and merged[i][0] < o.end:
            hidden += max(0.0, min(o.end, merged[i][1])
                          - max(o.start, merged[i][0]))
            i += 1
        exposed += (o.end - o.start) - hidden
    return exposed, n


def _self_times(events):
    """Exclusive durations of nested intervals [(start, end), ...]
    sorted by start (ties: longer first)."""
    out = [e[1] - e[0] for e in events]
    stack = []            # (index, end, covered_until)
    for i, (s, e) in enumerate(events):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            j = stack[-1][0]
            out[j] -= min(e, events[j][1]) - s
        stack.append((i, e, s))
    return [max(0.0, x) for x in out]


def union_length(intervals, lo=None, hi=None) -> float:
    """Length of the union of intervals, clipped to [lo, hi]."""
    tot, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                tot += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        tot += cur_e - cur_s
    return tot


def gaps(intervals, lo: float, hi: float):
    """Idle gaps [(start, end)] in [lo, hi] not covered by intervals."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def latest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def ops_metadata(hlo_texts) -> dict:
    """{program name: {instruction name: [(instruction text, op_name),
    ...]}} from the optimised HLO text of every program the run
    executed; programs of one name (a prefill per padded length) share
    an entry."""
    meta: dict = {}
    for text in hlo_texts:
        mod = _MODULE.match(text)
        prog = meta.setdefault(mod.group(1) if mod else "", {})
        for line in text.splitlines():
            m = _INSTR.match(line)
            if not m:
                continue
            name, rest = m.group(1), m.group(2)
            op = _OPNAME.search(rest)
            prog.setdefault(name, []).append(
                (rest.split(", metadata=")[0], op.group(1) if op else ""))
    return meta


def _path_of(instr: str, text: str, module: str, meta: dict) -> str:
    """The op_name of the instruction ``instr`` of program ``module``
    whose text the trace event carries."""
    cands = meta.get(module, {}).get(instr, ())
    if not cands:
        return ""
    body = text.split(" = ", 1)[1] if " = " in text else text
    for head, path in cands:
        if body.startswith(head[:120]) or head.startswith(body[:120]):
            return path
    return cands[0][1]


def reduce(path: str, meta: dict | None = None,
           span_prefix: str = "bench.") -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    meta = meta or {}
    modules, ops, spans, devices = [], [], [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            devices.append(dev)
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                           * 1e-9, module_base(e.name))
                          for e in lines.get("XLA Modules", ()))
            modules += [(dev,) + x for x in mods]
            evs = sorted(((e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9, e.name)
                          for e in lines.get("XLA Ops", ())),
                         key=lambda x: (x[0], -x[1]))
            selfs = _self_times([(s, e) for s, e, _ in evs])
            k = 0
            for (s, e, text), st in zip(evs, selfs):
                while k + 1 < len(mods) and mods[k + 1][0] <= s:
                    k += 1
                mod = mods[k][2] if mods and mods[k][0] <= s else ""
                im = _EVENT_INSTR.match(text)
                instr = im.group(1) if im else text.split(" ", 1)[0]
                ops.append(Op(dev, s, e, st, instr, text, mod,
                              _path_of(instr, text, mod, meta)))
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(span_prefix):
                    s = e.start_ns * 1e-9
                    spans.append(Span(s, s + e.duration_ns * 1e-9, e.name))
    spans.sort(key=lambda x: x.start)
    window = ((spans[0].start, max(x.end for x in spans)) if spans
              else (0.0, 0.0))
    return Reduced(sorted(devices), modules, ops, spans, window)


def busy_s(red: Reduced, device: int) -> float:
    lo, hi = red.window
    return union_length([(o.start, o.end) for o in red.ops
                         if o.device == device], lo, hi)


def idle_gaps(red: Reduced, device: int):
    """[(seconds, span name active when the gap began)] of the device's
    idle gaps inside the window, longest first."""
    lo, hi = red.window
    out = []
    for a, b in gaps([(o.start, o.end) for o in red.ops
                      if o.device == device], lo, hi):
        active = [s.name for s in red.spans if s.start <= a < s.end]
        out.append((b - a, active[-1] if active else "none"))
    return sorted(out, reverse=True)
