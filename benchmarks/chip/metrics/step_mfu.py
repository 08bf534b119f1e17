"""Model FLOPs of the real tokens the step programs processed (prompt
tokens of each prefill, active slots of each decode; padding and idle
slots do not count), as the cell's architecture module counts them,
over the device time of the decode and prefill programs times chips
times the chip's bf16 peak, in percent."""
from harness import reading


def read(rec):
    if rec.trace is None:
        return None
    t = sum(reading.module_time(rec, m)[0] for m in reading.STEP_MODULES)
    if t <= 0:
        return None
    decode = reading.cost(rec, "decode_model_flops", "step_mfu")
    prefill = reading.cost(rec, "prefill_model_flops", "step_mfu")
    flops = 0.0
    for s in reading.traced_steps(rec):
        if s.ctx:
            flops += decode(s.ctx)
        for p in s.prefills:
            flops += prefill(p)
    return 100.0 * flops / (t * rec.chips * rec.peaks.bf16_flops)
