"""Share of the roofline bound reached by the fused MLP chain: per call
the gate, up and down weights plus the rows' activations, 6 rows d_model
d_ff flops (the architecture module's ``mlp_chain``), over the device
time of every op under ``jit(fused_mlp_chain)``, averaged over the
chips.  Rows are the active slots of a decode step and the real prompt
tokens of a prefill whose plan fused the chain.  On a mesh each chip
holds d_ff / chips of every chain and is charged that share."""
from harness import peaks, reading


def read(rec):
    if rec.trace is None:
        return None
    t = reading.kernel_time(rec, reading.MLP_CHAIN)
    if t <= 0:
        return None
    steps = reading.traced_steps(rec)
    rows = [len(s.ctx) for s in steps if s.ctx]
    if not any(reading.kernel_in_module(rec, reading.MLP_CHAIN,
                                        reading.DECODE)):
        rows = []
    fused = reading.kernel_in_module(rec, reading.MLP_CHAIN, reading.PREFILL)
    prefills = [p for s in steps for p in s.prefills]
    rows += [p for p, f in zip(prefills, fused) if f]
    if not rows:
        return None
    chain = reading.cost(rec, "mlp_chain", "mlp_chain_roofline")
    flops = nbytes = 0.0
    for m in rows:
        f, b = chain(m)
        flops, nbytes = flops + f, nbytes + b
    # each chip runs its own d_ff / chips share of every layer's chain
    flops, nbytes = flops / rec.chips, nbytes / rec.chips
    share, bound = peaks.roofline_share(flops, nbytes, t, rec.peaks)
    return share, f"{bound}-bound"
