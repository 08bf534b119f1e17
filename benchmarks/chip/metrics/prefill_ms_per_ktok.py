"""Device time of the prefill programs (``jit_prefill_paged``) per 1000
real prompt tokens prefilled in the traced sub-window."""
from harness import reading


def read(rec):
    if rec.trace is None:
        return None
    t, n = reading.module_time(rec, reading.PREFILL)
    toks = sum(sum(s.prefills) for s in reading.traced_steps(rec))
    return 1e6 * t / toks if n and toks else None
