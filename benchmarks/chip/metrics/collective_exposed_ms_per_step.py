"""Device time per decode step of the collective ops in the decode
program (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all, and their async start and done halves) that no compute op
on the same chip overlaps, averaged over the chips; nothing to read
where the decode program runs no collective (one chip)."""
from harness import reading, trace_reduce


def read(rec):
    red = rec.trace
    if red is None:
        return None
    _, steps = reading.module_time(rec, reading.DECODE)
    per_chip = [trace_reduce.exposed_collective_s(red, d, reading.DECODE)
                for d in red.devices]
    if not steps or not any(n for _, n in per_chip):
        return None
    return 1e3 * sum(s for s, _ in per_chip) / len(red.devices) / steps
