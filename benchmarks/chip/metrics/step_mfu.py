"""Model FLOPs of the real tokens the step programs processed (prompt
tokens of each prefill, active slots of each decode; padding and idle
slots do not count; ``costs``) over the device time of the decode and
prefill programs times chips times the chip's bf16 peak, in percent."""
from harness import costs, reading


def read(rec):
    if rec.trace is None:
        return None
    t = sum(reading.module_time(rec, m)[0] for m in reading.STEP_MODULES)
    if t <= 0:
        return None
    shape = rec.cell.shape
    flops = 0.0
    for s in reading.traced_steps(rec):
        if s.ctx:
            flops += costs.decode_model_flops(shape, s.ctx)
        for p in s.prefills:
            flops += costs.prefill_model_flops(shape, p)
    return 100.0 * flops / (t * rec.chips * rec.peaks.bf16_flops)
