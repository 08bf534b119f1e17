"""Fused attention Pallas kernel (paper Table III workloads).

The attention chain  S = Q K^T ; P = softmax(S) ; O = P V  is the flat
schedule class ``n(k,h)`` with an online-softmax epilogue: the n (key)
loop streams, the intermediate S tile lives only in VMEM, and the O row
is accumulated with running max/denominator rescaling
(Schedule.needs_rescale).  Unlike handwritten FlashAttention, the block
sizes (bq, bkv) are chosen by MCFuser's analytical search for each
concrete (M, N, D) — the paper's critique of FlashAttention is exactly
that it fixes K == H and never tunes the reduction tiling.

Supports GQA (kv-head sharing via BlockSpec index maps), causal and
sliding-window masks, and decode (queries at the tail of the cache).
Paged decode reads each slot's live pages in place from its layer of
the stacked page pool (``_paged_decode_kernel``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.perf_model import device_spec

LANES = 128
NEG_INF = -1e30
# Sentinel "position" for unallocated / out-of-range paged-KV slots:
# larger than any real position, so the (always-on) causal mask of the
# paged kernel rejects the slot for every query row.
INVALID_POS = 1 << 30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, o_acc, m_sc, l_sc, *,
                 n_kv_blocks, bq, bkv, offset, causal, window, scale,
                 q_prologue=None, k_prologue=None, o_epilogue=None):
    """``q_prologue``/``k_prologue``/``o_epilogue`` are the
    FusionStitching hook points (core/planner.py): tile-local
    elementwise expressions applied to the q/k tiles at load and to the
    normalized o tile before the store, so memory-bound glue around the
    attention chain (head norms, rotations, output scaling) rides
    inside the kernel instead of paying an HBM round trip."""
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    q = q_ref[0, 0]                       # (bq, d)
    k = k_ref[0, 0]                       # (bkv, d)
    if q_prologue is not None:
        q = q_prologue(q)
    if k_prologue is not None:
        k = k_prologue(k)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bkv)

    if causal or window > 0:
        i = pl.program_id(2)
        rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        cols = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = cols <= rows + offset
        if window > 0:
            mask &= cols > rows + offset - window
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[:, :1]                  # (bq, 1)
    l_prev = l_sc[:, :1]
    m_curr = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_curr)
    p = jnp.exp(s - m_new)                # (bq, bkv)
    corr = jnp.exp(m_prev - m_new)        # (bq, 1)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)

    o_acc[...] = (o_acc[...] * corr
                  + jnp.dot(p.astype(v_ref.dtype), v_ref[0, 0],
                            preferred_element_type=jnp.float32))
    m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
    l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(j == n_kv_blocks - 1)
    def _():
        l = l_sc[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows
        o = o_acc[...] / l
        if o_epilogue is not None:
            o = o_epilogue(o)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def _divisor_block(block: int, n: int) -> int:
    """The largest block of at most ``block`` rows that divides ``n``."""
    block = min(block, n)
    while n % block:
        block -= 1
    return block


def _attn_partial_kernel(q_ref, k_ref, v_ref, pos_ref, qpos_ref,
                         o_ref, m_ref, l_ref,
                         o_acc, m_sc, l_sc, *, n_kv_blocks, bq, bkv,
                         causal, window, scale):
    """Per-shard body of the ring (kv-sequence-sharded) regime.

    Identical online-softmax recurrence to ``_attn_kernel`` with two
    differences: masks are evaluated against GLOBAL positions (query
    rows come from ``qpos_ref``, key columns from ``pos_ref`` — the
    shard's slice of the global kv index space — so a causal or
    windowed boundary can fall anywhere inside a shard, and paged
    callers can hand every batch row its own position vectors), and the
    epilogue emits the raw combine state
    ``(o_unnormalized, running_max, running_sum)`` instead of
    normalizing, so shards merge associatively via log-sum-exp
    (docs/design.md §7)."""
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    q = q_ref[0, 0]                       # (bq, d)
    k = k_ref[0, 0]                       # (bkv, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bkv)

    if causal or window > 0:
        rows = qpos_ref[0]                # (bq, 1) global q positions
        cols = pos_ref[0]                 # (1, bkv) global kv positions
        mask = cols <= rows
        if window > 0:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[:, :1]                  # (bq, 1)
    l_prev = l_sc[:, :1]
    m_curr = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_curr)
    p = jnp.exp(s - m_new)                # (bq, bkv)
    corr = jnp.exp(m_prev - m_new)        # (bq, 1)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)

    o_acc[...] = (o_acc[...] * corr
                  + jnp.dot(p.astype(v_ref.dtype), v_ref[0, 0],
                            preferred_element_type=jnp.float32))
    m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
    l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(j == n_kv_blocks - 1)
    def _():
        # Rows masked across this ENTIRE shard still accumulated
        # p = exp(NEG_INF - NEG_INF) = 1 per masked key; zero them so
        # the shard emits the merge identity (0, NEG_INF, 0) instead of
        # a spurious sum.  (Rows only partially masked are safe: the
        # first unmasked block's rescale multiplies the junk by
        # exp(NEG_INF - finite) = 0.)
        dead = m_sc[:, :1] <= NEG_INF * 0.5
        o_ref[0, 0] = jnp.where(dead, 0.0, o_acc[...])  # unnorm., f32
        m_ref[0, 0] = m_sc[:, :1]
        l_ref[0, 0] = jnp.where(dead, 0.0, l_sc[:, :1])


@functools.partial(jax.jit, static_argnames=(
    "bq", "bkv", "causal", "window", "scale", "row_start", "interpret"))
def fused_attention_partial(q: jax.Array, k: jax.Array, v: jax.Array,
                            kv_pos: jax.Array | None = None,
                            q_pos: jax.Array | None = None,
                            bq: int = 128, bkv: int = 128,
                            causal: bool = False, window: int = 0,
                            scale: float | None = None,
                            row_start: int = 0,
                            interpret: bool = False
                            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One shard's partial softmax-attention over its local kv slice.

    q: (B, Hq, M, D), k/v: (B, Hkv, N_local, D/Dv).  ``kv_pos``
    holds the GLOBAL position of each local kv slot — shape
    (N_local,) shared across the batch (default ``arange``) or
    (B, N_local) per request (the paged layout, where each request's
    page table maps its slots independently).  ``q_pos`` likewise is
    the global position of each query row, (M,) or (B, M); it defaults
    to ``row_start + arange`` (``row_start``: global position of q's
    first row).  Returns ``(o_unnorm, m_run, l_run)`` with

        o_unnorm (B, Hq, M, Dv) f32 = sum_n exp(s_n - m_run) * v_n
        m_run    (B, Hq, M, 1)  f32 = running max of masked scores
        l_run    (B, Hq, M, 1)  f32 = sum_n exp(s_n - m_run)

    so that for any split of the kv axis the shards merge with the
    associative log-sum-exp combine (``dist.ring_dispatch.
    merge_partials``); a single shard over the whole kv followed by
    ``finalize_partials`` reproduces ``fused_attention`` exactly.
    Rows entirely masked within this shard come back as
    ``(0, NEG_INF, 0)`` — the identity element of the merge.
    """
    b, hq, m, d = q.shape
    _, hkv, n, dv = v.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if kv_pos is None:
        kv_pos = jnp.arange(n, dtype=jnp.int32)
    if q_pos is None:
        q_pos = row_start + jnp.arange(m, dtype=jnp.int32)
    bq = _divisor_block(bq, m)
    bkv = _divisor_block(bkv, n)
    # positions ride as (rows, 1, N) and (rows, M, 1): every block's
    # last two dims are then (1, bkv) and (bq, 1), which Mosaic accepts
    # for any batch (a (1, bkv) block of a (B, N) array is refused at
    # B > 1: the second-last block dim must be 8-aligned or whole)
    kv_pos3 = kv_pos.astype(jnp.int32).reshape(-1, 1, n)
    q_pos3 = q_pos.astype(jnp.int32).reshape(-1, m, 1)
    kvb, qb = kv_pos3.shape[0], q_pos3.shape[0]
    grid = (b, hq, m // bq, n // bkv)

    kernel = functools.partial(
        _attn_partial_kernel, n_kv_blocks=n // bkv, bq=bq, bkv=bkv,
        causal=causal, window=window, scale=scale)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h, i, j: (b_, h // group, j, 0)),
            pl.BlockSpec((1, 1, bkv, dv),
                         lambda b_, h, i, j: (b_, h // group, j, 0)),
            pl.BlockSpec((1, 1, bkv),
                         (lambda b_, h, i, j: (b_, 0, j)) if kvb > 1
                         else (lambda b_, h, i, j: (0, 0, j))),
            pl.BlockSpec((1, bq, 1),
                         (lambda b_, h, i, j: (b_, i, 0)) if qb > 1
                         else (lambda b_, h, i, j: (0, i, 0))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, m, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, m, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, m, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=device_spec().vmem_budget,
        ),
        interpret=interpret,
        name="fused_attention_partial",
    )(q, k, v, kv_pos3, q_pos3)


@functools.partial(jax.jit, static_argnames=(
    "bq", "bkv", "causal", "window", "scale", "interpret",
    "q_prologue", "k_prologue", "o_epilogue"))
def fused_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    bq: int = 128, bkv: int = 128,
                    causal: bool = False, window: int = 0,
                    scale: float | None = None,
                    q_prologue=None, k_prologue=None, o_epilogue=None,
                    interpret: bool = False) -> jax.Array:
    """O = softmax(Q K^T * scale + mask) V, fused, GQA-aware.

    q: (B, Hq, M, D), k/v: (B, Hkv, N, D/Dv); Hq % Hkv == 0.
    Queries sit at the *tail* of the kv sequence (decode-compatible).
    ``q_prologue``/``k_prologue``/``o_epilogue``: optional tile-local
    elementwise stitching hooks (see ``_attn_kernel``).
    """
    b, hq, m, d = q.shape
    _, hkv, n, dv = v.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bq = min(bq, m)
    bkv = min(bkv, n)
    assert m % bq == 0 and n % bkv == 0, (m, n, bq, bkv)
    offset = n - m
    grid = (b, hq, m // bq, n // bkv)

    kernel = functools.partial(
        _attn_kernel, n_kv_blocks=n // bkv, bq=bq, bkv=bkv,
        offset=offset, causal=causal, window=window, scale=scale,
        q_prologue=q_prologue, k_prologue=k_prologue,
        o_epilogue=o_epilogue)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h, i, j: (b_, h // group, j, 0)),
            pl.BlockSpec((1, 1, bkv, dv),
                         lambda b_, h, i, j: (b_, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv),
                               lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, m, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=device_spec().vmem_budget,
        ),
        interpret=interpret,
        name="fused_attention",
    )(q, k, v)


def _paged_decode_kernel(layer_ref, tbl_ref, len_ref, q_ref, k_hbm, v_hbm,
                         o_ref, l_ref, k_buf, v_buf, sems,
                         o_acc, m_sc, l_sc, *, max_pages, page_size,
                         bkv, group, window, scale):
    """One slot's decode row per q-head over its live pages, read in
    place from layer ``layer_ref[0]`` of the stacked pool.

    Block ``j`` covers kv columns ``j*bkv .. (j+1)*bkv - 1``: its
    ``bkv // page_size`` pages are copied whole (every kv-head at once)
    from HBM into one half of ``k_buf``/``v_buf`` while the block
    before it is computed from the other half.  Only the
    ``ceil(lengths[b] / bkv)`` blocks that hold live positions are
    visited; the ones past them are wholly masked, and skipping them
    leaves ``(o, m, l)`` bitwise unchanged.  Each q-head row runs
    ``_attn_partial_kernel``'s recurrence on the block of its kv-head,
    so a row's arithmetic is the gather path's, and a copied block
    serves every q-head of its group."""
    b = pl.program_id(0)
    ppb = bkv // page_size
    row = len_ref[b] - 1                  # the query's global position
    n_blocks = jnp.clip((row + bkv) // bkv, 0, max_pages // ppb)
    first = b * max_pages                 # row b of the flat page table
    layer = layer_ref[0]

    def copies(j, slot):
        out = []
        for p in range(ppb):
            # unallocated entries read the scratch page, rejected below
            page = jnp.maximum(tbl_ref[first + j * ppb + p], 0)
            cols = pl.ds(p * page_size, page_size)
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, page], k_buf.at[slot, cols],
                sems.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[layer, page], v_buf.at[slot, cols],
                sems.at[1, slot]))
        return out

    o_acc[...] = jnp.zeros_like(o_acc)
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(n_blocks > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
    page_of_lane = lane // page_size

    def block(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        unallocated = jnp.zeros((1, bkv), jnp.bool_)
        for p in range(ppb):
            unallocated |= ((page_of_lane == p)
                            & (tbl_ref[first + j * ppb + p] < 0))
        cols = jnp.where(unallocated, INVALID_POS, j * bkv + lane)
        mask = cols <= row
        if window > 0:
            mask &= cols > row - window
        for kh in range(k_buf.shape[2]):
            # transposed once per kv-head, not once per q-head's product
            kt = k_buf[slot, :, kh, :].T          # (d, bkv)
            v = v_buf[slot, :, kh, :]             # (bkv, dv)
            for h in range(kh * group, (kh + 1) * group):
                s = jnp.dot(q_ref[0, h], kt,
                            preferred_element_type=jnp.float32) * scale
                s = jnp.where(mask, s, NEG_INF)   # (1, bkv)
                m_prev = m_sc[h][:, :1]
                l_prev = l_sc[h][:, :1]
                m_curr = jnp.max(s, axis=1, keepdims=True)
                m_new = jnp.maximum(m_prev, m_curr)
                p_ = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_new = l_prev * corr + jnp.sum(p_, axis=1, keepdims=True)
                o_acc[h] = (o_acc[h] * corr
                            + jnp.dot(p_.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32))
                m_sc[h] = jnp.broadcast_to(m_new, m_sc.shape[1:])
                l_sc[h] = jnp.broadcast_to(l_new, l_sc.shape[1:])
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    # rows masked everywhere emit the merge identity, as the partial
    # kernel's epilogue does
    dead = m_sc[:, :, :1] <= NEG_INF * 0.5
    o_ref[0] = jnp.where(dead, 0.0, o_acc[...])
    l_ref[0] = jnp.where(dead, 0.0, l_sc[:, :, :1])


def _paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                            layer, *, bkv, window, scale, interpret):
    """``(o_unnorm, l_run)`` of one query row per q-head over the live
    pages of each slot: grid ``(B,)``, the layer index, the page table
    and lengths as scalar prefetch, the stacked pools, ``(L, n_pages,
    page_size, Hkv, D)``, left in HBM and copied page by page."""
    b, hq, _, d = q.shape
    ps, hkv, dv = v_pages.shape[2:]
    max_pages = page_table.shape[1]
    kernel = functools.partial(
        _paged_decode_kernel, max_pages=max_pages, page_size=ps, bkv=bkv,
        group=hq // hkv, window=window, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hq, 1, d), lambda b_, *_: (b_, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, hq, 1, dv), lambda b_, *_: (b_, 0, 0, 0)),
                pl.BlockSpec((1, hq, 1, 1), lambda b_, *_: (b_, 0, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, bkv, hkv, d), k_pages.dtype),
                pltpu.VMEM((2, bkv, hkv, dv), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hq, 1, dv), jnp.float32),
                pltpu.VMEM((hq, 1, LANES), jnp.float32),
                pltpu.VMEM((hq, 1, LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, 1, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=device_spec().vmem_budget,
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      page_table.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      q, k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=(
    "bq", "bkv", "window", "scale", "pages_per_chunk", "interpret"))
def fused_attention_paged(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, page_table: jax.Array,
                          lengths: jax.Array, layer: jax.Array | None = None,
                          bq: int = 128, bkv: int = 128,
                          window: int = 0, scale: float | None = None,
                          pages_per_chunk: int = 0,
                          interpret: bool = False) -> jax.Array:
    """Fused attention over a paged KV cache (docs/serving.md).

    q: (B, Hq, M, D) — request b's query rows sit at the TAIL of its
    context, global positions ``lengths[b]-M .. lengths[b]-1`` (the
    serving decode convention; attention is causal by construction).
    k_pages/v_pages: (L, n_pages, page_size, Hkv, D/Dv), the stacked
    page pools of a layer scan (``serving.kv_pages``), read at layer
    ``layer`` (int32 scalar, traceable) in place; an unstacked pool
    (n_pages, page_size, Hkv, D/Dv) with ``layer=None`` is read as layer
    0 of a stack of one.  page_table: (B, max_pages) int32 physical page
    per logical page, -1 = unallocated; lengths: (B,) int32 context
    length per request.

    Decode (``M == 1``, one chunk, and a kv block of whole pages) runs
    ``_paged_decode_kernel``: each slot's live pages are read in place,
    one grid step per slot, nothing gathered.  Otherwise each chunk of
    the page table is gathered into the contiguous layout the fused
    schedule streams and run through ``fused_attention_partial`` with
    per-request global positions —
    unallocated slots carry the ``INVALID_POS`` sentinel the causal
    mask always rejects, and slots past ``lengths[b]`` (a partly
    filled tail page, possibly holding a previous tenant's stale kv)
    fail ``col <= row`` the same way.  Chunk states merge with the
    PR 4 log-sum-exp combine (``dist.ring_dispatch.merge_partials``).
    With the default single chunk both paths run, row for row, the
    recurrence ``fused_attention`` runs over the blocks of a contiguous
    cache of ``max_pages * page_size`` slots (the decode kernel skips
    only blocks wholly past ``lengths[b]``, which leave it unchanged),
    making the output bit-identical to the contiguous-cache kernel
    (tests/test_serving.py);
    ``pages_per_chunk`` bounds the gather staging buffer at the cost
    of one extra rescale per chunk boundary (f32-exact, not bitwise).
    """
    from ..dist.ring_dispatch import finalize_partials, merge_partials
    from ..serving.kv_pages import gather_pages, paged_kv_positions

    b, hq, m, d = q.shape
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    layer = jnp.asarray(layer, jnp.int32)
    ps = k_pages.shape[2]
    max_pages = page_table.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    blk = _divisor_block(bkv, max_pages * ps)
    if m == 1 and pages_per_chunk == 0 and blk % ps == 0:
        o, l_run = _paged_decode_attention(
            q, k_pages, v_pages, page_table, lengths, layer, bkv=blk,
            window=window, scale=scale, interpret=interpret)
        return finalize_partials(o, l_run, q.dtype)
    q_pos = (lengths.astype(jnp.int32)[:, None] - m
             + jnp.arange(m, dtype=jnp.int32)[None, :])
    cpp = (pages_per_chunk if 0 < pages_per_chunk < max_pages
           else max_pages)
    pad = (-max_pages) % cpp
    if pad:
        page_table = jnp.concatenate(
            [page_table, jnp.full((b, pad), -1, jnp.int32)], axis=1)
    state = None
    for c0 in range(0, page_table.shape[1], cpp):
        tbl = page_table[:, c0:c0 + cpp]                    # (B, C)
        kc = gather_pages(k_pages, tbl, layer)
        vc = gather_pages(v_pages, tbl, layer)
        kv_pos = paged_kv_positions(tbl, ps, invalid=INVALID_POS,
                                    first_page=c0)
        part = fused_attention_partial(
            q, kc, vc, kv_pos, q_pos, bq=bq, bkv=bkv,
            causal=True, window=window, scale=scale, interpret=interpret)
        state = part if state is None else merge_partials(state, part)
    o, _, l_run = state
    return finalize_partials(o, l_run, q.dtype)
