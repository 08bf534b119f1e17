"""Ring (kv-sequence-sharded) attention dispatch (docs/design.md §7).

The spatial dispatch in ``kernels.ops`` shards attention over batch and
heads — collective-free, but useless when ``batch x kv_heads`` cannot
cover the mesh or when one shard's HBM cannot hold the kv sequence.
This module executes the regime the analytical model has priced since
PR 2 (``tuner_mesh_spec(shard_reduction=True)``): split the kv axis —
the chain's cross-op *reduction* loop — across the tp-or-model axis,
run the partial-softmax fused kernel per shard
(``kernels.attention.fused_attention_partial``), and combine the
per-shard ``(o_unnormalized, running_max, running_sum)`` triples with
the associative log-sum-exp merge (FlashDecoding-style; the same wire
pattern as ``models.layers.distributed_decode_attention``).

The combine's executed collectives are exactly what
``core.perf_model.collective_bytes`` prices: one all-reduce of the
shard-local output (``num``) plus all-reduces of the two f32 per-row
statistics (``pmax`` of the max, ``psum`` of the rescaled sum) — both
sides evaluate ``core.ring.ring_traffic_bytes`` on the same buffers,
asserted against the compiled HLO in ``tests/test_ring_attention.py``.

``pipelined=True`` (this PR) replaces the blocking all-reduces with the
software-pipelined ring the tuner prices under
``MeshSpec(pipelined=True)``: after the global ``pmax`` (which no
rescale can precede), the rescaled ``(num, den)`` partials are chunked
``n`` ways over their rows and combined by a balanced ring
reduce-scatter — ``n - 1`` ``jax.lax.ppermute`` hops, each merging the
arriving accumulator with the local chunk while the next hop's chunk
is independent and free to overlap — then the owner finalizes its
chunk and a ring all-gather broadcasts the finished chunks back
(``n - 1`` more hops).  Executed wire: ``2(n-1)`` (+ ``n - 1`` for the
f32 sum statistic) collective-permutes of one chunk each — exactly
``core.perf_model.pipelined_collective_bytes``, asserted against the
compiled HLO like the serial combine.  Semantics are identical up to
f32 summation order: each ring chunk folds the same rescaled addends
as the serial ``psum`` but starting from a rotated shard, so outputs
agree to a few ulps (and bit-exactly across devices — the all-gather
replicates one owner's bits).  ``combine_partials`` is the
order-canonical host-level spec of the combine both paths implement.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .sharding import Rules, ring_dispatch_spec


@dataclasses.dataclass(frozen=True)
class RingPlan:
    """One viable ring dispatch: where the kv axis splits and the
    MeshSpec the tuner prices it under."""

    spec: object                  # core.perf_model.MeshSpec
    batch_axes: tuple[str, ...]
    axis: str                     # mesh axis carrying the kv split
    n_shards: int


def plan_ring_attention(rules: Rules, mesh: jax.sharding.Mesh, *,
                        batch: int, kv_len: int,
                        feature_dims: tuple[int, ...] = ()
                        ) -> Optional[RingPlan]:
    """The ring regime for this mesh, or None when no mesh axis can
    split ``kv_len`` evenly (then only the spatial regime exists)."""
    spec, baxes, ax = ring_dispatch_spec(rules, mesh, batch=batch,
                                         kv_len=kv_len,
                                         feature_dims=feature_dims)
    if ax is None:
        return None
    return RingPlan(spec=spec, batch_axes=baxes, axis=ax,
                    n_shards=mesh.shape[ax])


# ---------------------------------------------------------------------------
# log-sum-exp combine — pure functions, shared by the shard_map body,
# the host-level tests, and any future pipelined (true ring-pass) variant
# ---------------------------------------------------------------------------

def merge_partials(a, b):
    """Associative merge of two partial-softmax states.

    Each state is ``(o_unnorm, m, l)`` as emitted by
    ``fused_attention_partial`` (stat arrays broadcastable against
    ``o_unnorm``'s leading dims).  Commutative and associative — shard
    order cannot change the result beyond f32 rounding — with identity
    ``(0, -inf, 0)``, which is what fully-masked shards emit."""
    oa, ma, la = a
    ob, mb, lb = b
    m = jnp.maximum(ma, mb)
    ca = jnp.exp(ma - m)
    cb = jnp.exp(mb - m)
    return oa * ca + ob * cb, m, la * ca + lb * cb


def finalize_partials(o, l, dtype) -> jax.Array:
    """Normalize a (fully merged) partial state into the attention
    output; rows masked everywhere (l == 0) come out as zeros, matching
    the fused kernel's fully-masked-row convention."""
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l).astype(dtype)


def combine_partials(parts, dtype):
    """Order-canonical combine of per-shard partial states — the exact
    arithmetic of the executed pmax/psum combine, as a pure function.

    ``parts``: iterable of ``(shard_index, (o_unnorm, m, l))`` in ANY
    arrival order (a ring delivers partials in a rotation; a failure
    retry might permute them arbitrarily).  The result is
    bit-identical for every arrival order by construction: the global
    max is an exact, order-free reduction; each shard is rescaled once
    against it (the same single-rescale the dispatch performs — NOT the
    iterative ``merge_partials`` fold, whose per-step rescales compose
    ``exp`` in a different association); and the rescaled addends are
    summed left-to-right in shard-index order — the association XLA's
    ``psum`` uses (device-order linear reduction), which is what makes
    this twin bitwise-comparable to the executed serial combine.
    ``dtype`` is the wire dtype the numerator is cast to before
    summing, matching ``ring_attention``'s ``num``."""
    parts = [p for _, p in sorted(parts, key=lambda sp: sp[0])]
    if not parts:
        raise ValueError("combine_partials needs at least one shard")
    m_glob = parts[0][1]
    for _, m, _ in parts[1:]:
        m_glob = jnp.maximum(m_glob, m)
    num = den = None
    for o, m, l in parts:
        corr = jnp.exp(m - m_glob)
        ni = (o * corr).astype(dtype)
        di = l * corr
        num = ni if num is None else num + ni
        den = di if den is None else den + di
    return finalize_partials(num.astype(jnp.float32), den, dtype)


def _ring_combine_pipelined(num, den, axis, n_shards, out_dtype):
    """The pipelined combine body (module doc): balanced ring
    reduce-scatter of the rescaled ``(num, den)`` partials, owner-side
    finalize, ring all-gather of the finished chunks.

    ``num``: (..., Dv) at the wire dtype, ``den``: (...) f32 — both
    already rescaled by ``exp(m_local - m_glob)``.  Rows (the flattened
    leading dims) must divide ``n_shards``; regime planners gate on
    this.  Chunk ``c``'s accumulator starts at shard ``c+1`` and folds
    left-associatively around the ring — same addends as the serial
    ``psum``, rotated association — and every device returns the same
    bits (the all-gather replicates the owner's finalized chunk)."""
    n = n_shards
    lead, dv = num.shape[:-1], num.shape[-1]
    rows = math.prod(lead)
    assert rows % n == 0, (lead, n)
    c = rows // n
    x = num.reshape(n, c, dv)
    y = den.reshape(n, c)
    d = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    idx0 = jnp.mod(d - 1, n)
    acc_n = jax.lax.dynamic_index_in_dim(x, idx0, 0, keepdims=False)
    acc_d = jax.lax.dynamic_index_in_dim(y, idx0, 0, keepdims=False)
    for t in range(n - 1):
        # arriving partial chunk merges with the local contribution;
        # the chunk needed at hop t+1 is independent of this hop's
        # wire, which is the overlap eq (2') prices
        acc_n = jax.lax.ppermute(acc_n, axis, perm)
        acc_d = jax.lax.ppermute(acc_d, axis, perm)
        idx = jnp.mod(d - 2 - t, n)
        acc_n = acc_n + jax.lax.dynamic_index_in_dim(x, idx, 0,
                                                     keepdims=False)
        acc_d = acc_d + jax.lax.dynamic_index_in_dim(y, idx, 0,
                                                     keepdims=False)
    own = finalize_partials(acc_n.astype(jnp.float32),
                            acc_d[..., None], out_dtype)
    out = jnp.zeros((n, c, dv), out_dtype)
    out = jax.lax.dynamic_update_index_in_dim(out, own, d, 0)
    cur = own
    for t in range(n - 1):
        cur = jax.lax.ppermute(cur, axis, perm)
        idx = jnp.mod(d - 1 - t, n)
        out = jax.lax.dynamic_update_index_in_dim(out, cur, idx, 0)
    return out.reshape(*lead, dv)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh: jax.sharding.Mesh, axis: str,
                   batch_axes: tuple[str, ...] = (),
                   causal: bool = False, window: int = 0,
                   scale: Optional[float] = None,
                   bq: int = 128, bkv: int = 128,
                   pipelined: bool = False,
                   interpret: bool = False) -> jax.Array:
    """softmax(QK^T)V with kv sharded along ``axis``; output replicated
    over that axis (sharded over ``batch_axes`` like the inputs).

    q: (B, Hq, M, D), k/v: (B, Hkv, N, D/Dv); N % mesh.shape[axis] == 0
    (callers gate via ``plan_ring_attention``).  ``bq``/``bkv`` are the
    tuned block sizes of the *local* sub-problem (the tuner localized
    the chain under the same MeshSpec this dispatch runs).

    Queries sit at the tail of the global kv sequence
    (decode-compatible, as in ``fused_attention``); each shard masks
    against global positions, so causal/window boundaries falling
    inside a shard are exact.

    ``pipelined`` swaps the blocking psum combine for the per-hop
    ppermute ring (``_ring_combine_pipelined``, module doc); the local
    partial compute and the global ``pmax`` are shared verbatim, so the
    pipelined output differs from serial only by the f32 summation
    rotation — within a few ulps, and identical across devices.
    Callers gate on ``B * Hq * M`` divisible by the axis size (the
    regime planner only offers ``ring-pipelined`` when it is).
    """
    from ..kernels.attention import fused_attention_partial

    b, hq, m, d = q.shape
    n = k.shape[2]
    n_shards = mesh.shape[axis]
    assert n % n_shards == 0, (n, n_shards)
    n_loc = n // n_shards
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    row_start = n - m
    bspec = batch_axes if batch_axes else None
    qs = P(bspec, None, None, None)
    kvs = P(bspec, None, axis, None)

    def body(ql, kl, vl):
        shard = jax.lax.axis_index(axis)
        kv_pos = shard * n_loc + jnp.arange(n_loc, dtype=jnp.int32)
        o, mm, ll = fused_attention_partial(
            ql, kl, vl, kv_pos, bq=bq, bkv=bkv, causal=causal,
            window=window, scale=scale, row_start=row_start,
            interpret=interpret)
        mm = mm[..., 0]                       # (B, Hq, M) f32
        ll = ll[..., 0]
        m_glob = jax.lax.pmax(mm, axis)
        corr = jnp.exp(mm - m_glob)
        # numerator rides the wire at the output dtype — the bytes the
        # model prices (all-reduce of the localized chain's O tensor)
        num_loc = (o * corr[..., None]).astype(ql.dtype)
        den_loc = ll * corr
        if pipelined:
            return _ring_combine_pipelined(num_loc, den_loc, axis,
                                           n_shards, ql.dtype)
        num = jax.lax.psum(num_loc, axis)
        den = jax.lax.psum(den_loc, axis)
        return finalize_partials(num, den[..., None], ql.dtype)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(qs, kvs, kvs), out_specs=qs,
        check_vma=False)(q, k, v)


def paged_ring_decode_attention(q, k_pages, v_pages, page_table,
                                positions, *, window: int, scale: float,
                                rules: Rules, mesh: jax.sharding.Mesh,
                                batch_axes=None,
                                pipelined: bool = False):
    """Paged decode attention with the page-table COLUMNS (logical
    pages — the kv reduction axis at page granularity) sharded over the
    tp-or-model axis (docs/serving.md).

    q: (B, Hq, 1, D); k_pages/v_pages: (n_pages, ps, Hkv, D) — the
    pools stay replicated (every shard holds them; the engine's writes
    land identically on each replica), but each shard *gathers* only
    its ``max_pages / n_shards`` slice of every request's table, so the
    per-shard HBM traffic — the dominant decode cost — is 1/n of the
    contiguous gather.  page_table: (B, max_pages), max_pages divisible
    by the axis size (callers gate); positions: (B,) each request's
    current row (-1 = inactive slot).

    The combine is the same partial-softmax pmax + two psums as
    ``models.layers.distributed_decode_attention`` and ``ring_attention``
    — the exact buffers ``core.perf_model.collective_bytes`` prices for
    the paged-ring regime.

    ``pipelined`` runs the per-hop ppermute combine instead (module
    doc; the paged-ring-pipelined regime).  The rescaled numerator is
    cast to the query dtype before riding the ring — the wire bytes the
    model prices — so bf16 configs trade one cast for overlapped hops
    (f32 configs are unaffected: the cast is the identity).  Callers
    gate on ``B * Hq`` rows divisible by the axis size.
    """
    axis = rules.model
    n_shards = mesh.shape[axis]
    b, hq, m, d = q.shape
    ps, hkv = k_pages.shape[1], k_pages.shape[2]
    group = hq // hkv
    mp = page_table.shape[1]
    assert mp % n_shards == 0, (mp, n_shards)
    mpl = mp // n_shards
    bspec = batch_axes if batch_axes else None
    qs = P(bspec, None, None, None)
    pgs = P(None, None, None, None)     # replicated page pools
    ts = P(bspec, axis)                 # table columns sharded
    pos_s = P(bspec)

    from ..serving.kv_pages import gather_pages, paged_kv_positions

    def body(qb, kpb, vpb, tb, posb):
        shard = jax.lax.axis_index(axis)
        kk = gather_pages(kpb, tb)          # (B_local, hkv, mpl*ps, d)
        vv = gather_pages(vpb, tb)
        bl = kk.shape[0]
        kv_pos = paged_kv_positions(tb, ps, first_page=shard * mpl)
        rows = posb.astype(jnp.int32)[:, None]          # (B, 1) == (B, m)
        qg = qb.reshape(bl, hkv, group * m, d)
        s = jnp.einsum("bhmd,bhnd->bhmn", qg, kk,
                       preferred_element_type=jnp.float32) * scale
        # every folded (hkv, group*m) query row belongs to the same
        # request position, so the (B, 1, 1, N) mask broadcasts
        mask = kv_pos[:, None, None, :] >= 0
        mask &= kv_pos[:, None, None, :] <= rows[:, None, :, None]
        if window > 0:
            mask &= (kv_pos[:, None, None, :]
                     > rows[:, None, :, None] - window)
        s = jnp.where(mask, s, -1e30)
        m_loc = jnp.max(s, axis=-1, keepdims=True)
        m_glob = jax.lax.pmax(m_loc, axis)
        p = jnp.exp(s - m_glob)
        l_loc = jnp.sum(p, axis=-1, keepdims=True)
        acc_loc = jnp.einsum("bhmn,bhnv->bhmv", p.astype(vv.dtype), vv,
                             preferred_element_type=jnp.float32)
        if pipelined:
            o = _ring_combine_pipelined(
                acc_loc.astype(qb.dtype), l_loc[..., 0], axis,
                n_shards, qb.dtype)
        else:
            l = jax.lax.psum(l_loc, axis)
            acc = jax.lax.psum(acc_loc, axis)
            o = finalize_partials(acc, l, qb.dtype)
        return o.reshape(bl, hq, m, vv.shape[-1])

    return jax.shard_map(
        body, mesh=mesh, in_specs=(qs, pgs, pgs, ts, pos_s),
        out_specs=qs, check_vma=False)(q, k_pages, v_pages, page_table,
                                       positions)
