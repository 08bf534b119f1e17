"""Fused THREE-GEMM chain Pallas kernel: G = ((A@B)@D)@F.

Demonstrates that MCFuser's schedule classes extend beyond the paper's
2-op examples (§III-A: "our analysis method naturally extends").  The
kernel realizes the flat-family schedule the tuner picks for 3-chains
(`n..k / h..` sweeps with both intermediates pinned in VMEM):

    grid (batch, m, n, k):
        C[m,n]    += A[m,k] @ B[k,n]          # k innermost
        at k end:  E[m,:]  += C[m,n] @ D[n,:] # E row accumulated
        at n end:  G[m,:]   = E[m,:] @ F      # G row written once

Neither C nor E ever touches HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.perf_model import device_spec


def _kernel(a_ref, b_ref, d_ref, f_ref, g_ref, c_acc, e_acc, *, nn, nk,
            prologue=None, epilogue=None):
    n_i = pl.program_id(2)
    k_i = pl.program_id(3)

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
            g = jnp.dot(e_acc[...].astype(f_ref.dtype), f_ref[0],
                        preferred_element_type=jnp.float32)
            if epilogue is not None:
                g = epilogue(g)
            g_ref[0] = g.astype(g_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                              "prologue", "epilogue"))
def fused_gemm_chain3(a: jax.Array, b: jax.Array, d: jax.Array,
                      f: jax.Array, bm: int = 128, bn: int = 128,
                      bk: int = 128, prologue=None, epilogue=None,
                      interpret: bool = False) -> jax.Array:
    """G = ((A@B)@D)@F fused.  a: (B,M,K), b: (B,K,N), d: (B,N,H),
    f: (B,H,G).  H and G stay full-width in VMEM (MBCI chains have
    small trailing dims; Rule 4 prunes schedules where they don't fit).
    ``prologue``/``epilogue``: optional tile-local elementwise
    stitching hooks, as in ``gemm_chain._chain_kernel``."""
    bsz, m, k = a.shape
    n = b.shape[-1]
    h = d.shape[-1]
    g = f.shape[-1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k)
    nn, nk = n // bn, k // bk

    kernel = functools.partial(_kernel, nn=nn, nk=nk,
                               prologue=prologue, epilogue=epilogue)
    return pl.pallas_call(
        kernel,
        grid=(bsz, m // bm, nn, nk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b_, i, ni, ki: (b_, i, ki)),
            pl.BlockSpec((1, bk, bn), lambda b_, i, ni, ki: (b_, ki, ni)),
            pl.BlockSpec((1, bn, h), lambda b_, i, ni, ki: (b_, ni, 0)),
            pl.BlockSpec((1, h, g), lambda b_, i, ni, ki: (b_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, g), lambda b_, i, ni, ki: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, m, g), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, h), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=device_spec().vmem_budget,
        ),
        interpret=interpret,
        name="fused_gemm_chain3",
    )(a, b, d, f)
