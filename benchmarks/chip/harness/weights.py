"""Random weights from the seed, made on the device in one jitted call.

``draw`` makes the published parametrisation from a table of leaf
shapes, which each architecture module (``arch/<name>.py``) gives:
matrices with the source's ``initializer_range`` as their standard
deviation, RMSNorm gains ``g`` near 1 (drawn, not all ones, so that
every norm's weight is exercised).  Matrices are drawn in float32 and
rounded once to the served dtype; gains are rounded to the same dtype
and kept in float32.  The reference and the program's adapter (the
module's ``to_program``) both start from this draw, so they see
identical values.  A program that scales by ``1 + w`` gets
``w = g - 1``, exact in float32 for ``g`` in [0.5, 2], which the
clipped draw guarantees.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GAIN_SD = 0.1


def seed_key(seed: int):
    """A key for any whole seed: the low and high 32 bits are folded in
    one after the other, so seeds past 2**32 stay distinct."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def is_gain(name: str) -> bool:
    return name.endswith("norm")


def draw(shapes: dict, key, dtype, init_sd: float) -> dict:
    """One leaf per entry of ``shapes``, the i-th name in sorted order
    drawn from ``fold_in(key, i)``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if is_gain(name):
            g = 1.0 + GAIN_SD * jnp.clip(z, -4.0, 4.0)
            out[name] = g.astype(dtype).astype(jnp.float32)
        else:
            out[name] = (z * init_sd).astype(dtype)
    return out


def check_layout(made, expected) -> None:
    """Raise unless ``made`` has the structure, shapes and dtypes of
    ``expected`` (the program's ``jax.eval_shape(init_params)``)."""
    ts_m, ts_e = jax.tree.structure(made), jax.tree.structure(expected)
    if ts_m != ts_e:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{ts_m} vs {ts_e}")
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(expected)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight leaf {a.shape}/{a.dtype} where the "
                             f"program has {b.shape}/{b.dtype}")
