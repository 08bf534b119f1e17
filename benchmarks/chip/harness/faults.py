"""Faults a served model can have on one chip, planted under the timed
path's dispatch (the engine instance's ``_exec``), so that the whole run
around them, and its ``correct``, is the benchmark's own.

Each fault takes what one call of ``_exec`` returned and its arguments,
and hands back the logits and cache the engine then sees.  The tests
plant them at a tiny size on the CPU; ``calibrate.py --fault`` plants
them at a cell's own size on the chip.
"""
from __future__ import annotations


def altered_token(phase, logits, cache, args):
    """A token altered where it is produced: every decode step's logits
    pushed toward one id."""
    if phase == "decode":
        logits = logits.at[:, 3].add(1e4)
    return logits, cache


def state_unchanged(phase, logits, cache, args):
    """A step that returns its state unchanged: decode hands back the
    KV cache it was given, so no decoded token's K/V is ever written."""
    if phase == "decode":
        cache = args[1]
    return logits, cache


def half_batch(phase, logits, cache, args):
    """Half of the batch left out: every other active slot (position
    at least 0) gets no logits of its own (zeros, so its token is id 0).
    Slots fill from the lowest free one, so a fixed half of the slot
    range can hold no request at all."""
    if phase == "decode":
        import jax.numpy as jnp
        active = args[3] >= 0
        left_out = active & (jnp.cumsum(active) % 2 == 0)
        logits = jnp.where(left_out[:, None], 0.0, logits).astype(
            logits.dtype)
    return logits, cache


FAULTS = {f.__name__: f for f in (altered_token, state_unchanged,
                                  half_batch)}


def plant(engine, fault) -> None:
    """Route every ``_exec`` of ``engine`` through ``fault``."""
    exec_ = engine._exec

    def tampered(phase, *args):
        logits, cache = exec_(phase, *args)
        return fault(phase, logits, cache, args)
    engine._exec = tampered
