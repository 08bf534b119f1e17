"""Random weights from the seed, made on the device in one jitted call.

``canonical`` draws the published parametrisation of a dense GQA
decoder: matrices with the source's ``initializer_range`` as their
standard deviation, RMSNorm gains ``g`` near 1 (drawn, not all ones, so
that every norm's weight is exercised).  Matrices are drawn in float32
and rounded once to the served dtype; gains are rounded to the same
dtype and kept in float32.  The reference and the program's adapter
both start from this draw, so they see identical values.

``to_program`` lays the draw out as the program's ``LM.init_params``
tree.  The program keeps an RMSNorm weight ``w`` and scales by
``1 + w`` (``models/layers.py``); ``w = g - 1`` is exact in float32
for ``g`` in [0.5, 2], which the clipped draw guarantees.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .costs import Shape

GAIN_SD = 0.1


def seed_key(seed: int):
    """A key for any whole seed: the low and high 32 bits are folded in
    one after the other, so seeds past 2**32 stay distinct."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_shapes(s: Shape, qk_norm: bool) -> dict:
    L, d, f = s.layers, s.d_model, s.d_ff
    hd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    shapes = {
        "embed": (s.vocab, d), "lm_head": (d, s.vocab),
        "final_norm": (d,),
        "attn_norm": (L, d), "wq": (L, d, hd), "wk": (L, d, kvd),
        "wv": (L, d, kvd), "wo": (L, hd, d), "mlp_norm": (L, d),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }
    if qk_norm:
        shapes["q_norm"] = (L, s.head_dim)
        shapes["k_norm"] = (L, s.head_dim)
    return shapes


def is_gain(name: str) -> bool:
    return name.endswith("norm")


def canonical(s: Shape, qk_norm: bool, key, dtype, init_sd: float) -> dict:
    out = {}
    for i, (name, shape) in enumerate(sorted(leaf_shapes(s, qk_norm).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if is_gain(name):
            g = 1.0 + GAIN_SD * jnp.clip(z, -4.0, 4.0)
            out[name] = g.astype(dtype).astype(jnp.float32)
        else:
            out[name] = (z * init_sd).astype(dtype)
    return out


def to_program(c: dict, qk_norm: bool) -> dict:
    """The program's parameter tree for a uniform stack of attention
    layers (one scanned super-block of pattern ``("attn",)``)."""
    mix = {"wq": c["wq"], "wk": c["wk"], "wv": c["wv"], "wo": c["wo"]}
    if qk_norm:
        mix["q_norm"] = c["q_norm"] - 1.0
        mix["k_norm"] = c["k_norm"] - 1.0
    layer = {"ln1": {"w": c["attn_norm"] - 1.0}, "mix": mix,
             "ln2": {"w": c["mlp_norm"] - 1.0},
             "ff": {"w_gate": c["w_gate"], "w_up": c["w_up"],
                    "w_down": c["w_down"]}}
    return {"embed": c["embed"], "final_norm": {"w": c["final_norm"] - 1.0},
            "lm_head": c["lm_head"], "stack": {"b0_attn": layer},
            "tail": []}


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
