"""Device idle time inside the harness's ``bench.step`` spans (one per
``engine.step()``), per step, in the traced sub-window: the host work of
a scheduler iteration that the device waits for."""
from harness import trace_reduce


def read(rec):
    red = rec.trace
    if red is None:
        return None
    steps = [s for s in red.spans if s.name == "bench.step"]
    if not steps:
        return None
    idle = 0.0
    for dev in red.devices:
        busy = [(o.start, o.end) for o in red.ops if o.device == dev]
        for s in steps:
            idle += (s.end - s.start) - trace_reduce.union_length(
                busy, s.start, s.end)
    return 1e3 * idle / len(red.devices) / len(steps)
