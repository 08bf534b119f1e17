"""Device time of one execution of the decode program
(``jit_decode_step_paged``), mean over the traced sub-window."""
from harness import reading


def read(rec):
    if rec.trace is None:
        return None
    t, n = reading.module_time(rec, reading.DECODE)
    return 1e3 * t / n if n else None
