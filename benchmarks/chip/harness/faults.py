"""Faults a served model can have, planted under the timed path's
dispatch (the engine instance's ``_exec``), so that the whole run around
them, and its ``correct``, is the benchmark's own.

Each fault takes what one call of ``_exec`` returned and its arguments,
and hands back the logits and cache the engine then sees; one of
``ON_PARAMS`` instead takes the parameters every dispatch is given and
hands back those the engine then serves with.  The tests
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


def exchange_left_out(params):
    """The exchange between chips left out: on a tensor-parallel mesh,
    which splits every layer's q-heads and d_ff, a missing all-reduce
    leaves each chip with its own partial sum of the attention and MLP
    outputs.  Planted as the first chip's share: the rows of every
    row-parallel matrix (``wo``, ``w_down``) that the other chips hold,
    as each leaf's own sharding splits them, are zeroed.

    It rewrites the parameters once, at planting, and not at every
    dispatch: a copy made per call would sit beside the engine's own
    (a quarter of ``wo`` and ``w_down``, 1.2 GB a chip for the whole
    Qwen3-8B) while the KV pool is still whole on every chip.  The rows
    are zeroed by a mask, which keeps each leaf's sharding, and not by a
    scatter, whose update (three quarters of the leaf) is made whole on
    one chip.  A cell on one chip has no exchange to leave out."""
    import jax
    import jax.numpy as jnp

    def first_share(w):
        keep = w.sharding.shard_shape(w.shape)[1]
        if keep == w.shape[1]:
            raise ValueError("exchange_left_out: these rows are not split "
                             "between chips; a one-chip cell has no "
                             "exchange to leave out")
        def zero_rows(x):
            rows = jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[1], 1), 1)
            return jnp.where(rows < keep, x, jnp.zeros((), x.dtype))
        return jax.jit(zero_rows, out_shardings=w.sharding)(w)

    def block(b):
        mix = dict(b["mix"], wo=first_share(b["mix"]["wo"]))
        ff = dict(b["ff"], w_down=first_share(b["ff"]["w_down"]))
        return dict(b, mix=mix, ff=ff)
    stack = {k: block(b) for k, b in params["stack"].items()}
    return dict(params, stack=stack)


ON_PARAMS = (exchange_left_out,)
FAULTS = {f.__name__: f for f in (altered_token, state_unchanged,
                                  half_batch, *ON_PARAMS)}


def plant(engine, fault) -> None:
    """Route every ``_exec`` of ``engine`` through ``fault``, or serve
    with the parameters a fault of ``ON_PARAMS`` makes."""
    if fault in ON_PARAMS:
        engine.params = fault(engine.params)
        return
    exec_ = engine._exec

    def tampered(phase, *args):
        logits, cache = exec_(phase, *args)
        return fault(phase, logits, cache, args)
    engine._exec = tampered
