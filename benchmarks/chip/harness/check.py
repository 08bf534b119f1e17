"""The comparison that decides ``correct``.

For a sample of the requests the window finished (drawn from the seed,
the longest always in it), the reference runs once over each prompt
followed by its served tokens.  At each position where the engine
served a token, the gap is the reference's best logit minus the
reference's logit of the served token: 0 where they agree, and a flip
between near-tied logits costs only the size of the tie.  The number
compared is the widest gap over every served token of the sample.

The control (``control=True``) reads, at the same positions, the gap of
the token that the float8 reference ranks first.
"""
from __future__ import annotations

import numpy as np

from . import reference, traffic_gen

BUCKET = 512          # sequences are padded to a multiple (fewer compiles)


def sequences(sample, traffic, vocab: int):
    """(tokens, targets, first served position) per sampled request."""
    out = []
    for r in sample:
        req = traffic_gen.Request(r.index, r.prompt_len, r.max_new, 0.0)
        prompt = traffic.tokens(req, vocab)
        served = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        tgt = np.zeros_like(seq)
        tgt[len(prompt) - 1:] = served
        out.append((seq, tgt, len(prompt) - 1))
    return out


def gaps(weights, cell, seqs, control: bool = False):
    """(widest served gap, widest control gap or None, tokens compared),
    by the reference of the cell's architecture module."""
    import jax.numpy as jnp
    key = reference.cfg_key(cell.config)
    worst, worst_ctl, n = 0.0, 0.0, 0
    for seq, tgt, first in seqs:
        s = len(seq)
        pad = -(-s // BUCKET) * BUCKET
        toks = np.zeros(pad, np.int32)
        toks[:s] = seq
        tg = np.zeros(pad, np.int32)
        tg[:s] = tgt
        g_s, g_c = reference.gaps(weights, jnp.asarray(toks),
                                  jnp.asarray(tg), cell.arch.hidden, key,
                                  control)
        g_s = np.asarray(g_s)[first:s]
        worst = max(worst, float(g_s.max()))
        if control:
            worst_ctl = max(worst_ctl, float(np.asarray(g_c)[first:s].max()))
        n += s - first
    return worst, (worst_ctl if control else None), n
