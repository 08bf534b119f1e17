"""The plain float32 reference that decides ``correct``: no kernels, no
cache, no batching; one sequence at a time, every matmul at
``precision=HIGHEST``.

Each architecture module (``arch/<name>.py``) gives the forward pass up
to the final norm, ``hidden(w, tokens, config, control)``; this file
unembeds it with the draw's ``lm_head`` (d_model, vocab) and reads the
gaps.  The helpers every module's pass shares are here too: RMSNorm
``x * rsqrt(mean(x^2) + eps) * g`` and the float8 rounding of the
control.

``control=True`` computes the same pass with every matmul operand
rounded to float8 e4m3 (per-row scales for activations, per-column for
weights, products summed in float32): the nearest precision below the
configuration's bfloat16, the step a later change would be tempted by.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0           # largest finite float8_e4m3fn


def fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along
    ``axis`` (the contracted dimension), back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def cfg_key(config: dict) -> str:
    """The whole configuration as a jit static argument: its JSON text,
    which hashes where the dict (with lists such as ``layer_types``)
    does not."""
    return json.dumps(config, sort_keys=True)


ROWS = 256           # rows of logits made at once


@functools.partial(jax.jit,
                   static_argnames=("hidden", "cfg_key", "control"))
def gaps(w, tokens, targets, hidden, cfg_key: str, control: bool = False):
    """Per position p of ``tokens`` (S,): the gap by which the logit of
    ``targets[p]`` lies below the reference's best logit, and with
    ``control`` also the gap of the token the control ranks first.
    ``hidden`` is the architecture module's forward pass.  S must be a
    multiple of ROWS; positions past the real length are causal padding
    and are ignored by the caller."""
    cfg = json.loads(cfg_key)
    with jax.default_matmul_precision("highest"):
        ref_h = hidden(w, tokens, cfg, control=False)
        ctl_h = hidden(w, tokens, cfg, control=True) if control else ref_h
        head = w["lm_head"].astype(jnp.float32)
        head8 = fp8(head, 0) if control else head
        s, d = ref_h.shape

        def rows(args):
            rh, ch, tg = args
            ref = jnp.dot(rh, head, precision=HI)
            best = jnp.max(ref, -1)
            g_served = best - jnp.take_along_axis(ref, tg[:, None], -1)[:, 0]
            if not control:
                return g_served, jnp.zeros_like(g_served)
            ctl = jnp.dot(fp8(ch, -1), head8, precision=HI)
            pick = jnp.argmax(ctl, -1)
            g_ctl = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
            return g_served, g_ctl

        n = s // ROWS
        g_s, g_c = jax.lax.map(rows, (ref_h.reshape(n, ROWS, d),
                                      ctl_h.reshape(n, ROWS, d),
                                      targets.reshape(n, ROWS)))
    return g_s.reshape(s), g_c.reshape(s)


@functools.partial(jax.jit,
                   static_argnames=("hidden", "cfg_key", "control"))
def logits(w, tokens, hidden, cfg_key: str, control: bool = False):
    """All logits (S, V) of one sequence; for tests at small sizes."""
    with jax.default_matmul_precision("highest"):
        h = hidden(w, tokens, json.loads(cfg_key), control)
        head = w["lm_head"].astype(jnp.float32)
        if control:
            h, head = fp8(h, -1), fp8(head, 0)
        return jnp.dot(h, head, precision=HI)
