"""1 - (union of device op intervals) / traced window, in percent,
averaged over the chips."""
from harness import trace_reduce


def read(rec):
    red = rec.trace
    if red is None or red.window_s <= 0:
        return None
    busy = sum(trace_reduce.busy_s(red, d) for d in red.devices) / len(
        red.devices)
    return 100.0 * (1.0 - busy / red.window_s)
