"""Share of the roofline bound reached by decode attention over the
paged cache: the needed work (K/V of the positions below each active
slot's length, q and o, 4 ctx heads head_dim flops; the architecture
module's ``paged_attention``) over the device time of every op under
``jit(fused_attention_paged)`` in the decode program, averaged over the
chips.  On a mesh each chip attends with heads / chips of the heads
(paged-spatial) or pages / chips of the pages (paged-ring) and is
charged that share."""
from harness import peaks, reading


def read(rec):
    if rec.trace is None:
        return None
    t = reading.kernel_time(rec, reading.PAGED_ATTENTION, reading.DECODE)
    steps = [s for s in reading.traced_steps(rec) if s.ctx]
    if t <= 0 or not steps:
        return None
    attention = reading.cost(rec, "paged_attention", "paged_attn_roofline")
    flops = nbytes = 0.0
    for s in steps:
        f, b = attention(s.ctx)
        flops, nbytes = flops + f, nbytes + b
    # each chip attends with its own share of the heads (or of the pages)
    flops, nbytes = flops / rec.chips, nbytes / rec.chips
    share, bound = peaks.roofline_share(flops, nbytes, t, rec.peaks)
    return share, f"{bound}-bound"
