"""Fused GEMM-chain Pallas kernels — the paper's core artifact.

E = (A @ B) @ D computed in ONE kernel, never materializing C in HBM.
Two kernel families implement the two live schedule classes that survive
Rule 1/2 pruning (see core/dag.py):

* ``deep``  — sub-tiling expression ``nk`` (e.g. mhnk): grid over
  (batch, m, h, n, k); C is recomputed for every h-block (the redundancy
  MCFuser's model charges, which Chimera's data-movement-only model
  misses).
* ``flat``  — sub-tiling expression ``n(k,h)`` (e.g. mn(k,h)): grid over
  (batch, m, n, k); C is computed once per (m, n) and swept against the
  full H extent, with the E row accumulated in VMEM.

Memory hoisting (paper §III-B) appears as BlockSpec index-map
degeneracy: a Load hoisted out of a loop simply ignores that grid axis,
and Mosaic keeps the block resident in VMEM across those steps.

Tile sizes come from `core.search.heuristic_search` — the kernels are
schedule-parametrized, not hand-tuned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.perf_model import device_spec


def _chain_kernel(a_ref, b_ref, d_ref, e_ref, c_acc, e_acc, *, nn, nk,
                  n_axis, prologue=None, epilogue=None):
    """Per-block program  n{ k{ C += A@B }, E += C@D }.

    Shared by both styles: the grid prefix differs ((b,m,h) deep vs
    (b,m) flat) but the inner (n, k) machine is identical; `n_axis` is
    the grid position of n (k is n_axis + 1).

    ``prologue``/``epilogue`` are the FusionStitching hook points
    (core/planner.py): tile-local elementwise expressions applied to
    the A tile at load and to the finished E tile before the store —
    memory-bound glue rides inside the kernel instead of costing an
    HBM round trip.  Tile-local means the glue must be expressible
    per-tile; glue reducing over a tiled loop is not stitchable here
    (the planner's vmem/locality gate keeps such glue standalone)."""
    n_i = pl.program_id(n_axis)
    k_i = pl.program_id(n_axis + 1)

    @pl.when(k_i == 0)
    def _():
        c_acc[...] = jnp.zeros_like(c_acc)

    a = a_ref[0]
    if prologue is not None:
        a = prologue(a)
    c_acc[...] += jnp.dot(a, b_ref[0],
                          preferred_element_type=jnp.float32)

    @pl.when(k_i == nk - 1)
    def _():
        @pl.when(n_i == 0)
        def _():
            e_acc[...] = jnp.zeros_like(e_acc)
        e_acc[...] += jnp.dot(c_acc[...].astype(d_ref.dtype), d_ref[0],
                              preferred_element_type=jnp.float32)

        @pl.when(n_i == nn - 1)
        def _():
            e = e_acc[...]
            if epilogue is not None:
                e = epilogue(e)
            e_ref[0] = e.astype(e_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "bh", "style", "interpret",
                     "prologue", "epilogue"))
def fused_gemm_chain(a: jax.Array, b: jax.Array, d: jax.Array,
                     bm: int = 128, bn: int = 128, bk: int = 128,
                     bh: int = 128, style: str = "flat",
                     prologue=None, epilogue=None,
                     interpret: bool = False) -> jax.Array:
    """E = (A@B)@D fused.  a: (B, M, K), b: (B, K, N), d: (B, N, H).

    style="flat": bh is ignored (full-H row kept in VMEM — schedule
    class ``n(k,h)``); style="deep": (m, h) grid — class ``nk``.
    Tile sizes must divide the dims (ops.py pads per Rule 3 otherwise).
    ``prologue``/``epilogue``: optional tile-local elementwise
    callables stitched around the chain (see ``_chain_kernel``).
    """
    bsz, m, k = a.shape
    n = b.shape[-1]
    h = d.shape[-1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    bh = min(bh, h)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and h % bh == 0, (
        f"tiles must divide dims: {(m, n, k, h)} vs {(bm, bn, bk, bh)}")
    nn, nk = n // bn, k // bk

    if style == "deep":
        grid = (bsz, m // bm, h // bh, nn, nk)
        kernel = functools.partial(_chain_kernel, nn=nn, nk=nk, n_axis=3,
                                   prologue=prologue, epilogue=epilogue)
        in_specs = [
            pl.BlockSpec((1, bm, bk), lambda b_, i, j, ni, ki: (b_, i, ki)),
            pl.BlockSpec((1, bk, bn), lambda b_, i, j, ni, ki: (b_, ki, ni)),
            pl.BlockSpec((1, bn, bh), lambda b_, i, j, ni, ki: (b_, ni, j)),
        ]
        out_spec = pl.BlockSpec((1, bm, bh), lambda b_, i, j, ni, ki: (b_, i, j))
        scratch = [pltpu.VMEM((bm, bn), jnp.float32),
                   pltpu.VMEM((bm, bh), jnp.float32)]
    elif style == "flat":
        grid = (bsz, m // bm, nn, nk)
        kernel = functools.partial(_chain_kernel, nn=nn, nk=nk, n_axis=2,
                                   prologue=prologue, epilogue=epilogue)
        in_specs = [
            pl.BlockSpec((1, bm, bk), lambda b_, i, ni, ki: (b_, i, ki)),
            pl.BlockSpec((1, bk, bn), lambda b_, i, ni, ki: (b_, ki, ni)),
            pl.BlockSpec((1, bn, h), lambda b_, i, ni, ki: (b_, ni, 0)),
        ]
        out_spec = pl.BlockSpec((1, bm, h), lambda b_, i, ni, ki: (b_, i, 0))
        scratch = [pltpu.VMEM((bm, bn), jnp.float32),
                   pltpu.VMEM((bm, h), jnp.float32)]
    else:
        raise ValueError(f"unknown style {style!r}")

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, m, h), a.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 2)
            + ("arbitrary", "arbitrary"),
            vmem_limit_bytes=device_spec().vmem_budget,
        ),
        interpret=interpret,
        name="fused_gemm_chain",
    )(a, b, d)


# ---------------------------------------------------------------------------
# Gated-MLP chain (core/planner.py's carved chain.mlp_chain)
# ---------------------------------------------------------------------------

_ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
}


def _mlp_kernel(a_ref, wu_ref, wg_ref, wd_ref, e_ref, h_acc, g_acc,
                e_acc, *, nn, nk, n_axis, act, prologue, epilogue):
    """n{ k{ H += A@Wu ; G += A@Wg }, E += (act(G)*H) @ Wd }.

    The gated activation is the chain's attached epilogue
    (chain.mlp_chain): applied per finished (m, n) block in VMEM, so
    the d_ff-wide intermediate never touches HBM — the same flat/deep
    block machine as ``_chain_kernel`` with one extra accumulator."""
    n_i = pl.program_id(n_axis)
    k_i = pl.program_id(n_axis + 1)

    @pl.when(k_i == 0)
    def _():
        h_acc[...] = jnp.zeros_like(h_acc)
        if g_acc is not None:
            g_acc[...] = jnp.zeros_like(g_acc)

    a = a_ref[0]
    if prologue is not None:
        a = prologue(a)
    h_acc[...] += jnp.dot(a, wu_ref[0],
                          preferred_element_type=jnp.float32)
    if g_acc is not None:
        g_acc[...] += jnp.dot(a, wg_ref[0],
                              preferred_element_type=jnp.float32)

    @pl.when(k_i == nk - 1)
    def _():
        @pl.when(n_i == 0)
        def _():
            e_acc[...] = jnp.zeros_like(e_acc)
        if g_acc is not None:
            hidden = _ACTS[act](g_acc[...]) * h_acc[...]
        else:
            hidden = _ACTS[act](h_acc[...])
        e_acc[...] += jnp.dot(hidden.astype(wd_ref.dtype), wd_ref[0],
                              preferred_element_type=jnp.float32)

        @pl.when(n_i == nn - 1)
        def _():
            e = e_acc[...]
            if epilogue is not None:
                e = epilogue(e)
            e_ref[0] = e.astype(e_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("act", "bm", "bn", "bk", "bh", "style", "interpret",
                     "prologue", "epilogue"))
def fused_mlp_chain(a: jax.Array, wu: jax.Array, wd: jax.Array,
                    wg: jax.Array | None = None, act: str = "silu",
                    bm: int = 128, bn: int = 128, bk: int = 128,
                    bh: int = 128, style: str = "flat",
                    prologue=None, epilogue=None,
                    interpret: bool = False) -> jax.Array:
    """E = (act(A@Wg) * (A@Wu)) @ Wd fused (gated; ``wg=None`` computes
    the ungated E = act(A@Wu) @ Wd).  a: (B, M, K); wu/wg: (B, K, N);
    wd: (B, N, H).  Same two schedule classes, tile-size contract and
    stitching hooks as ``fused_gemm_chain``; tuned through
    ``core.api.fuse_mlp_chain``."""
    bsz, m, k = a.shape
    n = wu.shape[-1]
    h = wd.shape[-1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    bh = min(bh, h)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and h % bh == 0, (
        f"tiles must divide dims: {(m, n, k, h)} vs {(bm, bn, bk, bh)}")
    nn, nk = n // bn, k // bk
    gated = wg is not None
    if not gated:
        wg = wu  # dead operand; keeps one grid/spec layout for both

    def bind(n_axis):
        return functools.partial(
            _mlp_kernel, nn=nn, nk=nk, n_axis=n_axis, act=act,
            prologue=prologue, epilogue=epilogue)

    if style == "deep":
        grid = (bsz, m // bm, h // bh, nn, nk)
        kernel = bind(3)
        in_specs = [
            pl.BlockSpec((1, bm, bk), lambda b_, i, j, ni, ki: (b_, i, ki)),
            pl.BlockSpec((1, bk, bn), lambda b_, i, j, ni, ki: (b_, ki, ni)),
            pl.BlockSpec((1, bk, bn), lambda b_, i, j, ni, ki: (b_, ki, ni)),
            pl.BlockSpec((1, bn, bh), lambda b_, i, j, ni, ki: (b_, ni, j)),
        ]
        out_spec = pl.BlockSpec((1, bm, bh),
                                lambda b_, i, j, ni, ki: (b_, i, j))
        scratch = [pltpu.VMEM((bm, bn), jnp.float32),
                   pltpu.VMEM((bm, bn), jnp.float32),
                   pltpu.VMEM((bm, bh), jnp.float32)]
    elif style == "flat":
        grid = (bsz, m // bm, nn, nk)
        kernel = bind(2)
        in_specs = [
            pl.BlockSpec((1, bm, bk), lambda b_, i, ni, ki: (b_, i, ki)),
            pl.BlockSpec((1, bk, bn), lambda b_, i, ni, ki: (b_, ki, ni)),
            pl.BlockSpec((1, bk, bn), lambda b_, i, ni, ki: (b_, ki, ni)),
            pl.BlockSpec((1, bn, h), lambda b_, i, ni, ki: (b_, ni, 0)),
        ]
        out_spec = pl.BlockSpec((1, bm, h), lambda b_, i, ni, ki: (b_, i, 0))
        scratch = [pltpu.VMEM((bm, bn), jnp.float32),
                   pltpu.VMEM((bm, bn), jnp.float32),
                   pltpu.VMEM((bm, h), jnp.float32)]
    else:
        raise ValueError(f"unknown style {style!r}")

    def wrapped(a_ref, wu_ref, wg_ref, wd_ref, e_ref, h_acc, g_acc, e_acc):
        kernel(a_ref, wu_ref, wg_ref, wd_ref, e_ref, h_acc,
               g_acc if gated else None, e_acc)

    return pl.pallas_call(
        wrapped,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, m, h), a.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 2)
            + ("arbitrary", "arbitrary"),
            vmem_limit_bytes=device_spec().vmem_budget,
        ),
        interpret=interpret,
        name="fused_mlp_chain",
    )(a, wu, wg, wd)
