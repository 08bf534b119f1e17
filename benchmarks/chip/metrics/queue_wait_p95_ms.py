"""95th percentile, over requests due inside the window, of the wait
from the due time to the start of the ``bench.step`` that admitted the
request (host clock); one not admitted by the window's end counts at
(end - due)."""
from harness import reading


def read(rec):
    waits = []
    for r in reading.due_in_window(rec):
        t = (rec.steps[r.admit_step].t0 if 0 <= r.admit_step < len(rec.steps)
             else None)
        waits.append((t if t is not None and t <= rec.t_end
                      else rec.t_end) - r.due)
    v = reading.percentile(waits, 95)
    return None if v is None else 1e3 * v
