"""Arithmetic the metric readers share: percentiles over all samples,
token times in the window, device time from the reduced trace, and the
cell's architecture module's costs.

A percentile is NumPy's default (linear between the two nearest order
statistics) over every sample, never a median of per-chunk figures.
"""
from __future__ import annotations

import numpy as np

DECODE = "jit_decode_step_paged"
PREFILL = "jit_prefill_paged"
STEP_MODULES = (DECODE, PREFILL)
PAGED_ATTENTION = "fused_attention_paged"
MLP_CHAIN = "fused_mlp_chain"


def percentile(values, q: float) -> float | None:
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else None


def window_tokens(rec) -> int:
    return sum(1 for r in rec.requests for t in r.times if rec.in_window(t))


def itl_gaps(rec) -> list:
    """Gaps (s) between consecutive tokens of each request, for gaps
    that end inside the window."""
    out = []
    for r in rec.requests:
        for a, b in zip(r.times, r.times[1:]):
            if rec.in_window(b):
                out.append(b - a)
    return out


def due_in_window(rec) -> list:
    return [r for r in rec.requests if rec.t_start <= r.due < rec.t_end]


def ttft(rec) -> list:
    """Scheduled arrival to first token held by the host, for requests
    due in the window; one with no first token by the window's end
    counts at (end - due)."""
    out = []
    for r in due_in_window(rec):
        first = r.times[0] if r.times else None
        out.append((first if first is not None and first <= rec.t_end
                    else rec.t_end) - r.due)
    return out


def traced_steps(rec) -> list:
    a, b = rec.traced_steps
    return rec.steps[a:b]


def module_time(rec, name: str) -> tuple[float, int]:
    """(device seconds, executions) of the programs named ``name``,
    averaged over the chips in the trace."""
    red = rec.trace
    mods = [m for m in red.modules if m[3] == name
            and red.window[0] <= m[1] <= red.window[1]]
    n_dev = max(1, len(red.devices))
    return sum(m[2] - m[1] for m in mods) / n_dev, len(mods) // n_dev


def kernel_time(rec, path_part: str, module: str | None = None) -> float:
    """Device seconds (self time) of ops whose jit path holds
    ``path_part``, averaged over the chips."""
    red = rec.trace
    n_dev = max(1, len(red.devices))
    return sum(o.self_s for o in red.ops if path_part in o.path
               and (module is None or o.module == module)) / n_dev


def kernel_in_module(rec, path_part: str, module: str) -> list:
    """For each execution of ``module`` (in order, first chip), whether
    it ran an op under ``path_part``."""
    red = rec.trace
    dev = red.devices[0]
    mods = sorted((m[1], m[2]) for m in red.modules
                  if m[0] == dev and m[3] == module)
    hits = [o.start for o in red.ops if o.device == dev
            and o.module == module and path_part in o.path]
    return [any(a <= t <= b for t in hits) for a, b in mods]


def cost(rec, name: str, metric: str):
    """The function ``name`` of the cell's architecture module, with the
    configuration bound.  A module without it cannot price ``metric``:
    that raises, naming both, rather than reading 0."""
    arch = rec.cell.arch
    fn = getattr(arch, name, None)
    if fn is None:
        raise NotImplementedError(
            f"{metric}: architecture module {arch.__file__} has no "
            f"{name}(config, ...) to count the work with")
    config = rec.cell.config
    return lambda *a: fn(config, *a)
