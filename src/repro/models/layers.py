"""Shared neural-net layers for all assigned architectures (pure JAX).

Every block is a pair of functions:
    init_<block>(rng, cfg)       -> params pytree
    <block>(params, x, ...)      -> activations
plus a specs_<block>(cfg, rules) -> PartitionSpec pytree mirroring params.

Attention integrates MCFuser as a first-class feature: the production
path streams KV blocks with online softmax using MCFuser-tuned block
sizes (the fused-kernel schedule), so the intermediate score matrix
never exists in HBM — on TPU this is the Pallas kernel itself; in the
dry-run it is the structurally equivalent lax.scan program, so the
roofline reflects the fused design (docs/design.md §3).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..dist.sharding import (Rules, batch_placement, constrain,
                             dispatch_mesh_spec)
from .config import ModelConfig

# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(rng, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


def _rmsnorm_f32(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """rmsnorm without the trailing downcast — the stitched-epilogue
    form (run_planned_layer): glue inside a carved unit computes wide
    and downcasts once at the unit boundary."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return _rmsnorm_f32(x, w, eps).astype(x.dtype)


def _layernorm_f32(x: jax.Array, w: jax.Array, b: jax.Array,
                   eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return (xf - mu) * jax.lax.rsqrt(var + eps) * w + b


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float) -> jax.Array:
    return _layernorm_f32(x, w, b, eps).astype(x.dtype)


def apply_norm(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p["w"], p["b"], cfg.norm_eps)
    return rmsnorm(x, p["w"], cfg.norm_eps)


def init_norm(cfg: ModelConfig, d: Optional[int] = None) -> dict:
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((d,), jnp.float32),
                "b": jnp.zeros((d,), jnp.float32)}
    return {"w": jnp.zeros((d,), jnp.float32)}


def specs_norm(cfg: ModelConfig, rules: Rules) -> dict:
    if cfg.norm == "layernorm":
        return {"w": P(), "b": P()}
    return {"w": P()}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def _rope_f32(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """rope without the trailing downcast (see _rmsnorm_f32)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # align to (..., S, H, Dh): add a heads axis; batch broadcasts freely
    if positions.ndim > 1:
        # per-request positions (B, S) -> (B, S, 1, half): exactly one
        # heads axis (the while-loop below would stop one dim short)
        cos, sin = cos[..., None, :], sin[..., None, :]
    while cos.ndim < x.ndim - 1:
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, Dh), positions: (S,) or (B, S)."""
    return _rope_f32(x, positions, theta).astype(x.dtype)


# ---------------------------------------------------------------------------
# Streaming (fused-schedule) attention — XLA twin of kernels/attention.py
# ---------------------------------------------------------------------------

def streaming_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        *, causal: bool, window: int, scale: float,
                        bkv: int, q_offset: int = 0,
                        kv_positions: Optional[jax.Array] = None,
                        unroll: bool = False) -> jax.Array:
    """softmax(QK^T)V scanning KV in blocks of `bkv` (online softmax).

    q: (B, H, M, D), k/v: (B, H, N, D).  Never materializes (M, N).
    kv_positions: (N,) absolute positions of cache slots (ring buffers);
    defaults to arange(N).  q rows are at positions q_offset + arange(M).
    """
    b, h, m, d = q.shape
    n = k.shape[2]
    bkv = min(bkv, n)
    while n % bkv:          # non-divisible seq (whisper's 1500 frames)
        bkv -= 1
    steps = n // bkv
    qf = q.astype(jnp.float32) * scale
    rows = q_offset + jnp.arange(m, dtype=jnp.int32)

    kc = jnp.moveaxis(k.reshape(b, h, steps, bkv, d), 2, 0)
    vc = jnp.moveaxis(v.reshape(b, h, steps, bkv, v.shape[-1]), 2, 0)

    # The block mask is derived from the loop counter INSIDE the body —
    # passing precomputed per-step positions as scan xs lets XLA hoist
    # and stack all (steps, B, H, bq, bkv) masks as a loop-invariant
    # temp (hundreds of MB at 4k+ context; found in the dry-run HLO).
    def body(carry, xs):
        i, m_run, l_run, acc = carry
        kb, vb = xs
        if kv_positions is None:
            pb = i * bkv + jnp.arange(bkv, dtype=jnp.int32)
        else:
            pb = jax.lax.dynamic_slice(kv_positions, (i * bkv,), (bkv,))
        s = jnp.einsum("bhmd,bhnd->bhmn", qf, kb.astype(jnp.float32))
        mask = pb[None, None, None, :] >= 0
        if causal or window > 0:
            mask &= pb[None, None, None, :] <= rows[None, None, :, None]
            if window > 0:
                mask &= pb[None, None, None, :] > (rows[None, None, :, None]
                                                   - window)
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + jnp.sum(pexp, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bhmn,bhnv->bhmv", pexp,
                                      vb.astype(jnp.float32))
        return (i + 1, m_new, l_new, acc), None

    init = (jnp.int32(0),
            jnp.full((b, h, m, 1), -1e30, jnp.float32),
            jnp.zeros((b, h, m, 1), jnp.float32),
            jnp.zeros((b, h, m, v.shape[-1]), jnp.float32))
    (_, m_run, l_run, acc), _ = jax.lax.scan(body, init, (kc, vc),
                                             unroll=steps if unroll else 1)
    l_safe = jnp.where(l_run == 0.0, 1.0, l_run)
    return (acc / l_safe).astype(q.dtype)


def naive_attention(q, k, v, *, causal, window, scale, q_offset=0,
                    kv_positions=None):
    """Unfused reference: materializes the (M, N) score matrix in HBM —
    the paper's baseline (what you get without MBCI fusion)."""
    b, h, m, d = q.shape
    n = k.shape[2]
    s = jnp.einsum("bhmd,bhnd->bhmn", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if kv_positions is None:
        kv_positions = jnp.arange(n, dtype=jnp.int32)
    rows = q_offset + jnp.arange(m, dtype=jnp.int32)
    mask = kv_positions[None, None, None, :] >= 0
    if causal or window > 0:
        mask &= kv_positions[None, None, None, :] <= rows[None, None, :, None]
        if window > 0:
            mask &= (kv_positions[None, None, None, :]
                     > rows[None, None, :, None] - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhmn,bhnv->bhmv", p.astype(v.dtype), v).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (GQA + qk_norm + RoPE + cache)
# ---------------------------------------------------------------------------

def init_attention(rng, cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    d, dh = cfg.d_model, cfg.dh
    r = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(r[0], (d, cfg.n_heads * dh), dt),
        "wk": dense_init(r[1], (d, cfg.n_kv_heads * dh), dt),
        "wv": dense_init(r[2], (d, cfg.n_kv_heads * dh), dt),
        "wo": dense_init(r[3], (cfg.n_heads * dh, d), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), jnp.float32)
        p["k_norm"] = jnp.zeros((dh,), jnp.float32)
    return p


def specs_attention(cfg: ModelConfig, rules: Rules) -> dict:
    s = {
        "wq": rules.spec("data", "model"),
        "wk": rules.spec("data", "model"),
        "wv": rules.spec("data", "model"),
        "wo": rules.spec("model", "data"),
    }
    if cfg.qk_norm:
        s["q_norm"] = P()
        s["k_norm"] = P()
    return s


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int,
                    window: Optional[int] = None, dtype=None) -> dict:
    """Cache pytree: {"k","v","pos"}; "pos" holds each slot's absolute
    position (-1 = empty) so full and ring (windowed) caches share one
    code path."""
    win = cfg.window if window is None else window
    n = min(max_len, win) if win else max_len
    dt = dtype or _dtype(cfg)
    return {
        "k": jnp.zeros((batch, cfg.n_kv_heads, n, cfg.dh), dt),
        "v": jnp.zeros((batch, cfg.n_kv_heads, n, cfg.dh), dt),
        "pos": jnp.full((n,), -1, jnp.int32),
    }


def attention_block(p: dict, x: jax.Array, cfg: ModelConfig, rules: Rules,
                    *, positions: jax.Array, cache: Optional[dict] = None,
                    window: Optional[int] = None, causal: bool = True,
                    bkv: int = 512, unroll: bool = False,
                    mesh: Optional[jax.sharding.Mesh] = None,
                    dist_decode: bool = False,
                    kernel_ops: bool = False
                    ) -> tuple[jax.Array, Optional[dict]]:
    """x: (B, S, D).  positions: (S,) absolute positions of x's tokens.
    window None -> cfg.window.  Returns (out, updated cache).

    kernel_ops: route cache-free attention through ``kernels.ops`` —
    the MCFuser-tuned kernel dispatched per shard via shard_map when a
    mesh is ambient (docs/design.md §7), instead of the XLA
    streaming-attention twin."""
    b, s, d = x.shape
    dh = cfg.dh
    win = cfg.window if window is None else window

    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(b, s, cfg.n_heads, dh)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(b, s, cfg.n_kv_heads, dh)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(b, s, cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = constrain(q.transpose(0, 2, 1, 3), rules, "batch", "tp", None, None)
    k = constrain(k.transpose(0, 2, 1, 3), rules, "batch", None, None, None)
    v = constrain(v.transpose(0, 2, 1, 3), rules, "batch", None, None, None)

    scale = 1.0 / math.sqrt(dh)
    group = cfg.n_heads // cfg.n_kv_heads

    if cache is not None:
        nc = cache["k"].shape[2]
        nm = mesh.shape[rules.model] if (mesh is not None
                                         and rules.model) else 1
        heads_sharded_cache = (cfg.n_kv_heads % max(nm, 1) == 0
                               and cfg.n_kv_heads >= nm)
        if (dist_decode and rules.enabled and mesh is not None
                and rules.model and s == 1 and nc % max(nm, 1) == 0
                and not heads_sharded_cache):
            # only for SEQ-sharded caches (mirrors cache_specs); a
            # heads-sharded cache already decodes locally per shard and
            # the seq-layout shard_map would force a full reshard
            # (measured 3-4x regressions on codeqwen/olmoe)
            # distributed flash-decode: cache write + partial-softmax
            # attention fused in one shard_map (SS Perf hillclimb #1)
            baxes = batch_placement(rules, mesh, b)
            o, knew, vnew, posnew = distributed_decode_attention(
                q, cache["k"], cache["v"], k, v, positions[0] % nc,
                positions, cache["pos"], causal=causal, window=win,
                scale=scale, rules=rules, mesh=mesh, batch_axes=baxes)
            cache = {"k": knew, "v": vnew, "pos": posnew}
            o = constrain(o, rules, "batch", "tp", None, None)
            o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * dh)
            out = jnp.einsum("bsh,hd->bsd", o, p["wo"])
            return constrain(out, rules, "batch", "seq", None), cache
        if win and s >= win:
            # prefill longer than the ring: only the last `win` tokens
            # can ever be attended to again
            ks, vs, ps_ = k[:, :, -win:], v[:, :, -win:], positions[-win:]
        else:
            ks, vs, ps_ = k, v, positions
        idx = ps_ % nc
        cache = {
            "k": cache["k"].at[:, :, idx].set(ks),
            "v": cache["v"].at[:, :, idx].set(vs),
            "pos": cache["pos"].at[idx].set(ps_),
        }
        if win and s >= win:
            # fresh long prefill: every row's window lies inside the
            # current k/v — the ring holds only the tail and would starve
            # early rows, so attend over the un-cached projections.
            kk = jnp.repeat(k, group, axis=1)
            vv = jnp.repeat(v, group, axis=1)
            kv_pos = positions
        else:
            kk = jnp.repeat(cache["k"], group, axis=1)
            vv = jnp.repeat(cache["v"], group, axis=1)
            kv_pos = cache["pos"]
        if cfg.use_fused_attention and kk.shape[2] > 2 * bkv and s > 1:
            o = streaming_attention(
                q, kk, vv, causal=causal, window=win, scale=scale,
                bkv=bkv, q_offset=positions[0], kv_positions=kv_pos,
                unroll=unroll)
        else:
            # decode / short: single-block scores are already tiny
            o = _positional_attention(q, kk, vv, positions, kv_pos,
                                      causal, win, scale)
    elif kernel_ops and s > 1:
        # sharded fused-kernel dispatch: GQA handled inside the kernel,
        # no head repeat; batch/heads shard per the ambient mesh + rules
        from ..kernels import ops as kernel_ops_mod
        o = kernel_ops_mod.attention(
            q, k, v, causal=causal, window=win, scale=scale,
            mesh=mesh if rules.enabled else None, rules=rules)
    else:
        kk = jnp.repeat(k, group, axis=1)
        vv = jnp.repeat(v, group, axis=1)
        if cfg.use_fused_attention and s > 2 * bkv:
            o = streaming_attention(q, kk, vv, causal=causal, window=win,
                                    scale=scale, bkv=bkv, q_offset=0,
                                    unroll=unroll)
        else:
            o = naive_attention(q, kk, vv, causal=causal, window=win,
                                scale=scale)

    o = constrain(o, rules, "batch", "tp", None, None)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * dh)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"])
    return constrain(out, rules, "batch", "seq", None), cache


def distributed_decode_attention(q, k_cache, v_cache, k_new, v_new, slot,
                                 rows_pos, kv_pos, *, causal, window,
                                 scale, rules, mesh, batch_axes):
    """Decode attention over a sequence-sharded KV cache WITHOUT
    gathering it (SS Perf hillclimb #1, iterations 1-4).

    * it1: per-shard partial softmax; combine = pmax + psum of the
      rescaled numerator/denominator (O(B x Hq x Dh) on the wire vs
      ~2x cache bytes for the baseline gather).
    * it3: GQA via reshape, not jnp.repeat (refuted: XLA had fused it).
    * it4a: bf16 score/PV einsums with f32 accumulation — the f32
      .astype copies of the cache slice were ~10 GB/step.
    * it4b: the new token's cache write happens INSIDE the shard_map on
      the owning shard only (lax.cond + local DUS).  Outside, GSPMD
      lowers a traced-index update of a sharded array to a full-slice
      masked rewrite (~2.5 GB/layer/step, found in the dry-run HLO).

    k_new/v_new: (B, Hkv, 1, D); slot: traced cache slot index.
    Returns (o, new_k_cache, new_v_cache, new_kv_pos).
    """
    bspec = batch_axes if batch_axes else None
    qs = P(bspec, None, None, None)
    ks = P(bspec, None, rules.model, None)
    ns = P(bspec, None, None, None)
    ps = P(rules.model)
    hq = q.shape[1]
    hkv = k_cache.shape[1]
    group = hq // hkv

    def f(qb, kb, vb, knb, vnb, pb):
        shard = jax.lax.axis_index(rules.model)
        ln = kb.shape[2]
        loc = slot - shard * ln
        ok = (loc >= 0) & (loc < ln)
        safe = jnp.clip(loc, 0, ln - 1)

        def write(args):
            kb_, vb_, pb_ = args
            kb_ = jax.lax.dynamic_update_slice(kb_, knb, (0, 0, safe, 0))
            vb_ = jax.lax.dynamic_update_slice(vb_, vnb, (0, 0, safe, 0))
            pb_ = jax.lax.dynamic_update_slice(
                pb_, rows_pos[-1:].astype(pb_.dtype), (safe,))
            return kb_, vb_, pb_

        kb, vb, pb = jax.lax.cond(ok, write, lambda a: a, (kb, vb, pb))

        b_, _, m_, d_ = qb.shape
        qg = qb.reshape(b_, hkv, group * m_, d_)
        s = jnp.einsum("bhmd,bhnd->bhmn", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        mask = pb[None, None, None, :] >= 0
        if causal or window > 0:
            mask &= pb[None, None, None, :] <= rows_pos[None, None, :, None]
            if window > 0:
                mask &= (pb[None, None, None, :]
                         > rows_pos[None, None, :, None] - window)
        s = jnp.where(mask, s, -1e30)
        m_loc = jnp.max(s, axis=-1, keepdims=True)
        m_glob = jax.lax.pmax(m_loc, rules.model)
        p = jnp.exp(s - m_glob)
        l = jax.lax.psum(jnp.sum(p, axis=-1, keepdims=True), rules.model)
        acc = jax.lax.psum(
            jnp.einsum("bhmn,bhnv->bhmv", p.astype(vb.dtype), vb,
                       preferred_element_type=jnp.float32), rules.model)
        l = jnp.where(l == 0.0, 1.0, l)
        o = (acc / l).reshape(b_, hq, m_, vb.shape[-1]).astype(qb.dtype)
        return o, kb, vb, pb

    return jax.shard_map(f, mesh=mesh,
                         in_specs=(qs, ks, ks, ns, ns, ps),
                         out_specs=(qs, ks, ks, ps),
                         check_vma=False)(q, k_cache, v_cache, k_new,
                                          v_new, kv_pos)


def _positional_attention(q, k, v, rows_pos, kv_pos, causal, window, scale):
    """Attention with explicit per-slot positions (decode over a cache)."""
    s = jnp.einsum("bhmd,bhnd->bhmn", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = kv_pos[None, None, None, :] >= 0
    if causal or window > 0:
        mask &= kv_pos[None, None, None, :] <= rows_pos[None, None, :, None]
        if window > 0:
            mask &= (kv_pos[None, None, None, :]
                     > rows_pos[None, None, :, None] - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhmn,bhnv->bhmv", p.astype(v.dtype), v).astype(q.dtype)


def _paged_positional_attention(q, k, v, rows_pos, kv_pos, window, scale):
    """``_positional_attention`` with PER-REQUEST position vectors —
    the paged-decode twin (docs/serving.md).  rows_pos: (B, M) global
    query positions (-1 = masked row); kv_pos: (B, N) global position
    of each gathered slot (-1 = unallocated).  Same op sequence as
    ``_positional_attention``, so a paged cache holding the same
    context as a contiguous one produces bit-identical output."""
    s = jnp.einsum("bhmd,bhnd->bhmn", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = kv_pos[:, None, None, :] >= 0
    mask &= kv_pos[:, None, None, :] <= rows_pos[:, None, :, None]
    if window > 0:
        mask &= (kv_pos[:, None, None, :]
                 > rows_pos[:, None, :, None] - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhmn,bhnv->bhmv", p.astype(v.dtype), v).astype(q.dtype)


def paged_attention_block(p: dict, x: jax.Array, cfg: ModelConfig,
                          rules: Rules, *, positions: jax.Array,
                          cache: dict, page_table: jax.Array,
                          window: Optional[int] = None,
                          mesh: Optional[jax.sharding.Mesh] = None,
                          dist_decode: bool = False,
                          dist_pipelined: bool = False,
                          kernel_ops: bool = False,
                          block: Optional[tuple] = None
                          ) -> tuple[jax.Array, dict]:
    """Attention over a paged KV cache (docs/serving.md).

    x: (B, S, D); positions: (B, S) absolute position of each row
    (-1 = masked: prompt padding or an inactive engine slot); cache:
    ``{"k_pages", "v_pages"}`` of shape (n_pages, page_size, Hkv, dh)
    — the shared pool, no batch dim — or, inside the layer scan, the
    stacked pools (L, n_pages, page_size, Hkv, dh) and the scan's
    ``"layer"`` index; page_table: (B, max_pages) physical page per
    logical page (-1 = unallocated).

    Projections/RoPE/GQA are identical to ``attention_block``; the kv
    write scatters this step's rows in place through
    ``serving.kv_pages.slot_coords`` (masked rows land on the scratch
    page) and attention runs over the
    page-table gather with per-request positions.  Serving is causal
    by construction.  Three bodies, one semantics (docs/design.md §3):
    the XLA twin (``_paged_positional_attention``), the fused kernel
    (``kernels.attention.fused_attention_paged``, ``kernel_ops`` /
    TPU), and the kv-sharded ring regime
    (``dist.ring_dispatch.paged_ring_decode_attention``) when
    ``dist_decode`` and a mesh with a model axis that divides the page
    table are present.
    """
    b, s, d = x.shape
    dh = cfg.dh
    win = cfg.window if window is None else window

    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(b, s, cfg.n_heads, dh)
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(b, s, cfg.n_kv_heads, dh)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"]).reshape(b, s, cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    cache = _write_kv(cache, page_table, positions, k, v)

    qt = q.transpose(0, 2, 1, 3)          # (B, Hq, S, dh)
    qt = constrain(qt, rules, "batch", "tp", None, None)
    scale = 1.0 / math.sqrt(dh)
    group = cfg.n_heads // cfg.n_kv_heads

    o = _paged_attention_body(qt, cache, page_table, positions,
                              group=group, win=win, scale=scale,
                              rules=rules, mesh=mesh,
                              dist_decode=dist_decode,
                              dist_pipelined=dist_pipelined,
                              kernel_ops=kernel_ops, block=block)

    o = constrain(o, rules, "batch", "tp", None, None)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * dh)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"])
    return constrain(out, rules, "batch", "seq", None), cache


def _write_kv(cache: dict, page_table: jax.Array, positions: jax.Array,
              k: jax.Array, v: jax.Array) -> dict:
    """Scatter this step's k/v rows (B, S, Hkv, dh) into the paged
    cache at their slots, in the layer ``cache["layer"]`` of a stacked
    pool when the scan hands one; the rest of the pool is untouched."""
    from ..serving import kv_pages as KP
    phys, off = KP.slot_coords(page_table, positions,
                               cache["k_pages"].shape[-3])
    layer = cache.get("layer")
    return dict(
        cache,
        k_pages=KP.scatter_pages(cache["k_pages"], phys, off, k, layer),
        v_pages=KP.scatter_pages(cache["v_pages"], phys, off, v, layer))


def _paged_attention_body(qt: jax.Array, cache: dict,
                          page_table: jax.Array, positions: jax.Array,
                          *, group: int, win: int, scale: float,
                          rules: Rules,
                          mesh: Optional[jax.sharding.Mesh] = None,
                          dist_decode: bool = False,
                          dist_pipelined: bool = False,
                          kernel_ops: bool = False,
                          block: Optional[tuple] = None) -> jax.Array:
    """The three-body paged attention core — ring regime, fused paged
    kernel, or the XLA gather twin; one semantics (docs/design.md §3).
    Shared verbatim by the hand-wired ``paged_attention_block`` and the
    planner executor (``run_planned_layer``), so a planned serving step
    is bit-identical to the hand-wired one by construction.

    qt: (B, Hq, S, dh) already transposed+constrained; cache holds the
    POST-write page pools.  Every body reads its layer from the stacked
    pools at ``cache["layer"]``; an unstacked pool (a tail site) is
    read as layer 0 of a stack of one."""
    from ..serving import kv_pages as KP

    b, _, s, _ = qt.shape
    kp, vp, layer = cache["k_pages"], cache["v_pages"], cache.get("layer")
    if layer is None:
        kp, vp, layer = kp[None], vp[None], jnp.int32(0)
    ps, hkv = kp.shape[2], kp.shape[3]
    nm = mesh.shape[rules.model] if (mesh is not None and rules.model) else 1
    mp = page_table.shape[1]

    def _twin() -> jax.Array:
        # the XLA gather twin: page-table gather + per-request
        # positional attention — the reference body every other regime
        # must match bit-identically (f32), and the shadow-verification
        # oracle for the fused branch below
        kk = jnp.repeat(KP.gather_pages(kp, page_table, layer),
                        group, axis=1)
        vv = jnp.repeat(KP.gather_pages(vp, page_table, layer),
                        group, axis=1)
        kv_pos = KP.paged_kv_positions(page_table, ps)
        return _paged_positional_attention(qt, kk, vv, positions, kv_pos,
                                           win, scale)

    if (dist_decode and rules.enabled and mesh is not None and rules.model
            and s == 1 and nm > 1 and mp % nm == 0):
        from ..dist.ring_dispatch import paged_ring_decode_attention
        baxes = batch_placement(rules, mesh, b)
        # every shard gathers all kv-heads of its pages, so the ring
        # takes its layer's pool whole on each chip: one layer made
        # whole where the carried stack is split by kv-head, not the
        # whole stack
        return paged_ring_decode_attention(
            qt, kp[layer], vp[layer], page_table, positions[:, 0],
            window=win, scale=scale, rules=rules,
            mesh=mesh, batch_axes=baxes, pipelined=dist_pipelined)
    if kernel_ops and s == 1 and jax.default_backend() == "tpu":
        # decode only: the kernel's tail convention needs q rows at
        # lengths-M..lengths-1, which padded prefill rows violate.
        # ``block`` carries the regime search's winning tiles, so the
        # executed schedule is the one the model priced.  Dispatch is
        # guarded like every kernel tail (``kernels.ops.guarded``): a
        # quarantined fingerprint or an injected fault degrades to the
        # bit-identical XLA gather twin below (docs/reliability.md),
        # while a kernel the compiler refuses raises.
        # On a mesh the kernel runs under shard_map (XLA cannot
        # partition a Mosaic kernel): batch over the data axes, heads
        # over tp-or-model where they divide — the placement the
        # paged-spatial regime was priced under.
        from ..kernels import ops as kernel_ops_mod
        from ..kernels.attention import fused_attention_paged
        bq, bkv = block if block is not None else (128, 128)
        fp = ("attn-paged", b, qt.shape[1], ps, mp, win, bq, bkv,
              str(qt.dtype))
        kernel = functools.partial(fused_attention_paged, bq=bq, bkv=bkv,
                                   window=win, scale=scale)
        if rules.enabled and mesh is not None:
            _, baxes, hax = dispatch_mesh_spec(
                rules, mesh, kind="attention", batch=b,
                feature_dims=(hkv, qt.shape[1]))
            bspec = baxes or None
            qs = P(bspec, hax, None, None)
            pools = P(None, None, None, hax, None)
            kernel = jax.shard_map(
                kernel, mesh=mesh,
                in_specs=(qs, pools, pools, P(bspec, None), P(bspec), P()),
                out_specs=qs, check_vma=False)
        return kernel_ops_mod.guarded(
            fp, lambda: kernel(qt, kp, vp, page_table,
                               positions[:, -1] + 1, layer),
            _twin)
    return _twin()


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def init_mlp(rng, cfg: ModelConfig, d: Optional[int] = None,
             ff: Optional[int] = None) -> dict:
    dt = _dtype(cfg)
    d = d or cfg.d_model
    ff = ff or cfg.d_ff
    r = jax.random.split(rng, 3)
    if cfg.act in ("swiglu", "geglu"):
        return {"w_gate": dense_init(r[0], (d, ff), dt),
                "w_up": dense_init(r[1], (d, ff), dt),
                "w_down": dense_init(r[2], (ff, d), dt)}
    return {"w_up": dense_init(r[0], (d, ff), dt),
            "w_down": dense_init(r[1], (ff, d), dt)}


def specs_mlp(cfg: ModelConfig, rules: Rules) -> dict:
    if cfg.act in ("swiglu", "geglu"):
        return {"w_gate": rules.spec("data", "model"),
                "w_up": rules.spec("data", "model"),
                "w_down": rules.spec("model", "data")}
    return {"w_up": rules.spec("data", "model"),
            "w_down": rules.spec("model", "data")}


def mlp_block(p: dict, x: jax.Array, cfg: ModelConfig, rules: Rules) -> jax.Array:
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif cfg.act == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    h = constrain(h, rules, "batch", None, "tp")
    return constrain(h @ p["w_down"], rules, "batch", None, None)


# ---------------------------------------------------------------------------
# Planner-driven layer execution (core/planner.py)
# ---------------------------------------------------------------------------

def run_planned_layer(lp, p: dict, x: jax.Array, cfg: ModelConfig,
                      rules: Rules, *, positions: jax.Array, rt,
                      cache: Optional[dict] = None,
                      page_table: Optional[jax.Array] = None
                      ) -> tuple[jax.Array, Optional[dict]]:
    """Execute one attention block from a planner ``LayerPlan`` — the
    zero-hand-specified-chains path behind ``Runtime(planner=True)``.

    Walks the plan's op DAG; every node dispatches to the *same* jnp
    code ``_apply_layer``'s hand-wired path runs (attention_block /
    paged_attention_block + mlp_block twins, verbatim), so a
    stitch-disabled plan is bit-identical to the hand-wired layer.
    Glue stitched into a carved chain as prologue/epilogue instead
    executes in f32 (the ``_*_f32`` twins — what a fused kernel's
    VMEM-resident epilogue computes in) with ONE downcast at the carved
    unit's boundary; on float32 configs that is still bitwise
    identical, on bf16 it differs only by where rounding lands
    (docs/planner.md).

    Serving phases: a plan traced with ``phase="prefill"``/``"decode"``
    carries a ``kv_write`` node — pass the paged ``cache``
    ({"k_pages","v_pages"}, plus the scan's ``"layer"`` over stacked
    pools) and ``page_table`` and the walk scatters this step's k/v
    in place through ``serving.kv_pages`` then runs the shared
    ``_paged_attention_body`` (ring / fused paged kernel / XLA twin —
    the same three-body dispatch the hand-wired block uses).
    Contiguous (non-paged) caches are priced by the planner but not
    executed here; ``models/lm.py`` keeps them hand-wired.

    Kernel dispatch: under ``rt.kernel_ops`` a *fused* planner-carved
    MLP chain routes through ``kernels.ops.mlp_chain`` (the tuned
    ``gemm_chain.fused_mlp_chain`` schedule on TPU, its XLA twin
    elsewhere); its stitched prologue/epilogue (ln2/res2) still
    execute f32-wide around the kernel call, exactly as in the node
    walk.

    lp: ``core.planner.LayerPlan`` (duck-typed; no core import here).
    p: the layer's param pytree ({"ln1","mix","ln2","ff"}).
    Returns ``(out, cache)`` — cache is the post-write pool dict for
    serving plans, or the ``cache`` argument passed in (None for the
    cache-free forward).
    """
    b, s, d = x.shape
    dh = cfg.dh
    dt = x.dtype
    pm, pf = p["mix"], p["ff"]
    win = cfg.window
    paged = cache is not None
    if paged and "k_pages" not in cache:
        raise NotImplementedError(
            "run_planned_layer executes paged serving caches only; "
            "contiguous-cache decode is served by the hand-wired path "
            "— models/lm.py takes it automatically (the planner branch "
            "skips non-paged caches), or force it explicitly with "
            "Runtime(planner=False)")
    if paged and page_table is None:
        raise ValueError("paged cache requires a page_table")

    stitched: set = set()
    downcast_at: set = set()
    for c in lp.chains:
        stitched.update(c.prologue)
        stitched.update(c.epilogue)
        if c.prologue or c.epilogue:
            # the unit computes wide past its stitched glue; cast back
            # to the model dtype exactly once, where the kernel's final
            # HBM store would round
            downcast_at.add(c.epilogue[-1] if c.epilogue else c.ops[-1])

    # Under kernel_ops, a fused MLP chain executes as ONE tuned kernel
    # call at its first op; the folded ops are skipped in the walk.
    mlp_unit = None
    mlp_folded: set = set()
    if rt.kernel_ops:
        mlp_unit = next((c for c in lp.chains
                         if c.kind == "mlp" and c.fused), None)
        if mlp_unit is not None:
            mlp_folded = set(mlp_unit.ops[1:])

    env: dict = {"x": x}
    for node in lp.nodes:
        nm, role, ins = node.name, node.role, node.ins
        if nm in mlp_folded:
            continue
        if mlp_unit is not None and nm == mlp_unit.ops[0]:
            from ..kernels import ops as kernel_ops_mod
            x2d = env[ins[0]].reshape(b * s, d)
            gated = cfg.act in ("swiglu", "geglu")
            wu, wd = pf["w_up"], pf["w_down"]
            wg = pf["w_gate"] if gated else None
            if wu.dtype != x2d.dtype:
                # a stitched ln2 prologue leaves x f32-wide; promote
                # the weights the way the XLA twin's matmul would
                wu, wd = wu.astype(x2d.dtype), wd.astype(x2d.dtype)
                wg = wg if wg is None else wg.astype(x2d.dtype)
            sharded = rules.enabled and rt.mesh is not None
            o2d = kernel_ops_mod.mlp_chain(
                x2d, wu, wd, w_gate=wg,
                act="silu" if cfg.act == "swiglu" else "gelu",
                mesh=rt.mesh if sharded else None,
                rules=rules if sharded else None)
            out = constrain(o2d.reshape(b, s, d), rules,
                            "batch", None, None)
            nm = mlp_unit.ops[-1]
            if nm in downcast_at:
                out = out.astype(dt)
            env[nm] = out
            continue
        if role == "norm":
            val = env[ins[0]]
            pn = p[nm]    # DAG node names ln1/ln2 mirror the param keys
            if nm in stitched:
                out = (_layernorm_f32(val, pn["w"], pn["b"], cfg.norm_eps)
                       if cfg.norm == "layernorm"
                       else _rmsnorm_f32(val, pn["w"], cfg.norm_eps))
            else:
                out = apply_norm(pn, val, cfg)
        elif role == "gemm":
            xin = env[ins[0]]
            if nm == "wq":
                out = jnp.einsum("bsd,dh->bsh", xin, pm["wq"]
                                 ).reshape(b, s, cfg.n_heads, dh)
            elif nm == "wk":
                out = jnp.einsum("bsd,dh->bsh", xin, pm["wk"]
                                 ).reshape(b, s, cfg.n_kv_heads, dh)
            elif nm == "wv":
                out = jnp.einsum("bsd,dh->bsh", xin, pm["wv"]
                                 ).reshape(b, s, cfg.n_kv_heads, dh)
            elif nm == "wo":
                out = jnp.einsum("bsh,hd->bsd", xin, pm["wo"])
                out = constrain(out, rules, "batch", "seq", None)
            elif nm in ("w_gate", "w_up"):
                out = xin @ pf[nm]
            elif nm == "w_down":
                out = constrain(xin @ pf["w_down"], rules,
                                "batch", None, None)
            else:
                raise ValueError(f"unknown gemm node {nm!r}")
        elif role == "qk_norm":
            w = pm["q_norm"] if nm.endswith("_q") else pm["k_norm"]
            val = env[ins[0]]
            out = (_rmsnorm_f32(val, w, cfg.norm_eps) if nm in stitched
                   else rmsnorm(val, w, cfg.norm_eps))
        elif role == "rope":
            val = env[ins[0]]
            out = (_rope_f32(val, positions, cfg.rope_theta)
                   if nm in stitched
                   else rope(val, positions, cfg.rope_theta))
        elif role == "kv_write":
            # scatter this step's k/v through to the paged pool — the
            # hand-wired block's write-through, verbatim (masked rows
            # land on the scratch page, serving/kv_pages.py); the
            # attention core then reads the cache, not these tensors
            if not paged:
                raise ValueError("kv_write node requires a paged cache")
            cache = _write_kv(cache, page_table, positions,
                              env[ins[0]], env[ins[1]])
            out = None
        elif role == "attn_qk":
            # the attention core executes as one unit here (fused chain
            # or not — fusion changes pricing and TPU kernel dispatch,
            # not the XLA twin): attention_block's cache-free
            # mid-section — or, for a serving plan, the shared
            # ``_paged_attention_body`` — verbatim
            q = constrain(env[ins[0]].transpose(0, 2, 1, 3), rules,
                          "batch", "tp", None, None)
            scale = 1.0 / math.sqrt(dh)
            group = cfg.n_heads // cfg.n_kv_heads
            if paged:
                o = _paged_attention_body(
                    q, cache, page_table, positions, group=group,
                    win=win, scale=scale, rules=rules, mesh=rt.mesh,
                    dist_decode=rt.dist_decode_attn,
                    dist_pipelined=rt.dist_decode_pipelined,
                    kernel_ops=rt.kernel_ops, block=rt.paged_block)
            elif rt.kernel_ops and s > 1:
                from ..kernels import ops as kernel_ops_mod
                k = constrain(env[ins[1]].transpose(0, 2, 1, 3), rules,
                              "batch", None, None, None)
                v = constrain(env["wv"].transpose(0, 2, 1, 3), rules,
                              "batch", None, None, None)
                o = kernel_ops_mod.attention(
                    q, k, v, causal=True, window=win, scale=scale,
                    mesh=rt.mesh if rules.enabled else None, rules=rules)
            else:
                k = constrain(env[ins[1]].transpose(0, 2, 1, 3), rules,
                              "batch", None, None, None)
                v = constrain(env["wv"].transpose(0, 2, 1, 3), rules,
                              "batch", None, None, None)
                kk = jnp.repeat(k, group, axis=1)
                vv = jnp.repeat(v, group, axis=1)
                if cfg.use_fused_attention and s > 2 * rt.bkv:
                    o = streaming_attention(q, kk, vv, causal=True,
                                            window=win, scale=scale,
                                            bkv=rt.bkv, q_offset=0,
                                            unroll=rt.unroll)
                else:
                    o = naive_attention(q, kk, vv, causal=True,
                                        window=win, scale=scale)
            o = constrain(o, rules, "batch", "tp", None, None)
            env["qk"] = env["softmax"] = None   # folded into this unit
            out = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * dh)
            nm = "pv"
        elif role in ("softmax", "attn_pv"):
            continue                            # handled at attn_qk
        elif role == "gate_act":
            if cfg.act in ("swiglu", "geglu"):
                act = jax.nn.silu if cfg.act == "swiglu" else jax.nn.gelu
                h = act(env[ins[0]]) * env[ins[1]]
            else:
                h = jax.nn.gelu(env[ins[0]])
            out = constrain(h, rules, "batch", None, "tp")
        elif role == "residual":
            mix, res = env[ins[0]], env[ins[1]]
            if nm in stitched:
                out = res.astype(jnp.float32) + mix.astype(jnp.float32)
            else:
                out = res + mix
        else:
            raise ValueError(f"unknown node role {role!r}")
        if nm in downcast_at:
            out = out.astype(dt)
        env[nm] = out

    out = env[lp.nodes[-1].name]
    out = out.astype(dt) if out.dtype != dt else out
    return out, cache


# ---------------------------------------------------------------------------
# Mixture of Experts (EP over the model axis via shard_map)
# ---------------------------------------------------------------------------

def init_moe(rng, cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    r = jax.random.split(rng, 4)
    p = {"router": dense_init(r[0], (d, e), jnp.float32),
         "w_up": dense_init(r[1], (e, d, ff), dt),
         "w_down": dense_init(r[2], (e, ff, d), dt)}
    if cfg.act == "swiglu":
        p["w_gate"] = dense_init(r[3], (e, d, ff), dt)
    return p


def specs_moe(cfg: ModelConfig, rules: Rules, n_model: int = 16) -> dict:
    e = cfg.moe.n_experts
    if rules.enabled and e % n_model == 0:
        w = rules.spec("model", None, None)      # EP: experts sharded
        w2 = rules.spec("model", None, None)
    else:
        w = rules.spec(None, "data", "model")    # TP on ffn dim
        w2 = rules.spec(None, "model", "data")
    s = {"router": P(), "w_up": w, "w_down": w2}
    if cfg.act in ("swiglu", "geglu"):
        s["w_gate"] = w
    return s


def _moe_local(p: dict, x2d: jax.Array, cfg: ModelConfig,
               expert_slice: Optional[tuple] = None,
               cap_slice: Optional[tuple] = None,
               scan_threshold: int = 1 << 27) -> jax.Array:
    """Token-choice top-k routing on a local token block.

    x2d: (T, D).  expert_slice: (start, count) of locally-owned experts
    (EP); None = all experts local.  cap_slice: (offset, size) window of
    each expert's capacity handled locally (EP replication when
    n_model > n_experts).  Returns the *partial* f32 output — caller
    reduces over the EP/TP axis.
    """
    moe = cfg.moe
    T, D = x2d.shape
    E, K = moe.n_experts, moe.top_k
    logits = (x2d.astype(jnp.float32) @ p["router"])
    probs = jax.nn.softmax(logits, axis=-1)               # (T, E)
    topw, topi = jax.lax.top_k(probs, K)                  # (T, K)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

    flat_e = topi.reshape(-1)                             # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_w = topw.reshape(-1)

    order = jnp.argsort(flat_e)                           # stable
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    first = jnp.searchsorted(se, jnp.arange(E, dtype=se.dtype))
    pos = jnp.arange(T * K, dtype=jnp.int32) - first[se]  # slot in expert

    cap = max(8, int(math.ceil(K * T * moe.capacity_factor / E / 8)) * 8)
    if expert_slice is not None:
        e0, e_loc = expert_slice
    else:
        e0, e_loc = 0, E
    if cap_slice is not None:
        c0, cap_loc = cap_slice
    else:
        c0, cap_loc = 0, cap
    local = (se >= e0) & (se < e0 + e_loc) & (pos >= c0) \
        & (pos < c0 + cap_loc)
    dest = jnp.where(local, (se - e0) * cap_loc + (pos - c0),
                     e_loc * cap_loc)

    slot_tok = jnp.zeros((e_loc * cap_loc + 1,), jnp.int32).at[dest].set(st)
    slot_w = jnp.zeros((e_loc * cap_loc + 1,), jnp.float32).at[dest].set(sw)
    slot_tok, slot_w = slot_tok[:-1], slot_w[:-1]

    act = jax.nn.silu if cfg.act == "swiglu" else jax.nn.gelu
    gated = cfg.act in ("swiglu", "geglu")

    if e_loc * cap_loc * D <= scan_threshold:
        # small enough: vectorized over local experts
        xe = jnp.take(x2d, slot_tok, axis=0).reshape(e_loc, cap_loc, D)
        if gated:
            h = (act(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]))
                 * jnp.einsum("ecd,edf->ecf", xe, p["w_up"]))
        else:
            h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, p["w_up"]))
        ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])   # (e_loc, cap, D)
        yflat = (ye.reshape(e_loc * cap_loc, D)
                 * slot_w[:, None].astype(ye.dtype))
        return jnp.zeros((T, D), jnp.float32).at[slot_tok].add(
            yflat.astype(jnp.float32))

    # big dispatch buffer (TP mode at 64k tokens): scan one expert at a
    # time so only a (cap, D) block is live, not (E, cap, D).  Outputs
    # are emitted as stacked ys and combined with ONE scatter-add — a
    # full (T, D) f32 carry would be read+written per expert step
    # (~17 GB/layer at mixtral train scale; SS Perf hillclimb #3).
    tok_e = slot_tok.reshape(e_loc, cap_loc)
    w_e = slot_w.reshape(e_loc, cap_loc)
    xs = {"tok": tok_e, "w": w_e, "w_up": p["w_up"], "w_down": p["w_down"]}
    if gated:
        xs["w_gate"] = p["w_gate"]

    @jax.checkpoint
    def step(_, ex):
        xe = jnp.take(x2d, ex["tok"], axis=0)             # (cap, D)
        if gated:
            h = (act(xe @ ex["w_gate"]) * (xe @ ex["w_up"]))
        else:
            h = jax.nn.gelu(xe @ ex["w_up"])
        ye = (h @ ex["w_down"]) * ex["w"][:, None].astype(h.dtype)
        return None, ye

    _, ys = jax.lax.scan(step, None, xs)                  # (e_loc, cap, D)
    out = jnp.zeros((T, D), jnp.float32).at[slot_tok].add(
        ys.reshape(e_loc * cap_loc, D).astype(jnp.float32))
    return out


def moe_block(p: dict, x: jax.Array, cfg: ModelConfig, rules: Rules,
              mesh: Optional[jax.sharding.Mesh]) -> jax.Array:
    """x: (B, S, D) -> (B, S, D).

    shard_map over (data x model): tokens batch-sharded over data and
    seq-sharded over model on entry (SP residual layout); an explicit
    all_gather over model assembles the local token block, the expert
    computation runs under one of three layouts, and a psum_scatter
    returns partial sums straight into the seq-sharded layout (half the
    traffic of a full psum, no re-scatter needed):

      * EP        (E % n_model == 0): e_loc experts per shard
      * EP-repl   (n_model % E == 0): every expert on n_model/E shards,
                  each owning a capacity slice
      * TP        (otherwise): all experts, ffn dim sliced
    """
    b, s, d = x.shape
    if not rules.enabled or mesh is None:
        return _moe_local(p, x.reshape(b * s, d), cfg
                          ).astype(x.dtype).reshape(b, s, d)

    n_model = mesh.shape[rules.model]
    e = cfg.moe.n_experts
    if rules.tp is None:
        # ZeRO-3 regime: batch rides every axis; expert weights are 2-D
        # sharded at rest and fully gathered per layer (no psum — each
        # shard routes only its own tokens)
        mode = "local"
        w_spec = wd_spec = P()
    elif e % n_model == 0:
        mode = "ep"
        w_spec = wd_spec = P(rules.model, None, None)
    else:
        mode = "tp"
        w_spec = P(None, None, rules.model)
        wd_spec = P(None, rules.model, None)
    batch_axes_eff = rules.batch_axes or rules.data
    dp_axes = tuple(a for a in batch_axes_eff if mesh.shape[a] > 1)
    batch_ok = b % math.prod(mesh.shape[a] for a in dp_axes) == 0 \
        if dp_axes else False
    seq_ok = (mode != "local" and rules.seq == rules.model
              and s % n_model == 0)
    x_in = P(dp_axes if (dp_axes and batch_ok) else None,
             rules.model if seq_ok else None, None)

    def fn(router, w_up, w_down, w_gate, xb):
        bl, sl, _ = xb.shape
        pl_ = {"router": router, "w_up": w_up, "w_down": w_down}
        if w_gate is not None:
            pl_["w_gate"] = w_gate
        if seq_ok:
            xb = jax.lax.all_gather(xb, rules.model, axis=1, tiled=True)
        x2d = xb.reshape(-1, d)
        if mode == "ep":
            idx = jax.lax.axis_index(rules.model)
            e_loc = e // n_model
            out = _moe_local(pl_, x2d, cfg,
                             expert_slice=(idx * e_loc, e_loc))
        else:
            out = _moe_local(pl_, x2d, cfg)
        out = out.astype(x.dtype)  # bf16 on the wire (EP partials are
        # disjoint token sets; TP partial sums tolerate bf16)
        if mode == "local":
            return out.reshape(bl, sl, d)   # tokens fully local: no psum
        if seq_ok:
            out = jax.lax.psum_scatter(out, rules.model,
                                       scatter_dimension=0, tiled=True)
        else:
            out = jax.lax.psum(out, rules.model)
        return out.reshape(bl, sl, d)

    w_gate = p.get("w_gate")
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(), w_spec, wd_spec,
                  w_spec if w_gate is not None else P(), x_in),
        out_specs=x_in,
        check_vma=False,
    )(p["router"], p["w_up"], p["w_down"], w_gate, x)


# ---------------------------------------------------------------------------
# Causal depthwise conv1d (Mamba-2 / RG-LRU frontends)
# ---------------------------------------------------------------------------

def causal_conv1d(x: jax.Array, w: jax.Array,
                  state: Optional[jax.Array] = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv.  x: (B, S, C), w: (K, C).
    state: (B, K-1, C) trailing context (decode).  Returns (y, new_state)."""
    b, s, c = x.shape
    k = w.shape[0]
    if state is None:
        state = jnp.zeros((b, k - 1, c), x.dtype)
    xin = jnp.concatenate([state, x], axis=1)          # (B, K-1+S, C)
    y = jnp.zeros((b, s, c), jnp.float32)
    for i in range(k):
        y = y + xin[:, i:i + s].astype(jnp.float32) * w[i].astype(jnp.float32)
    new_state = xin[:, -(k - 1):] if k > 1 else jnp.zeros((b, 0, c), x.dtype)
    return jax.nn.silu(y).astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# Mamba-2 (SSD — state-space duality, arXiv:2405.21060)
# ---------------------------------------------------------------------------

def init_mamba(rng, cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    s = cfg.ssm
    d = cfg.d_model
    din = s.expand * d
    H = din // s.head_dim
    proj = 2 * din + 2 * s.n_groups * s.d_state + H
    r = jax.random.split(rng, 4)
    return {
        "w_in": dense_init(r[0], (d, proj), dt),
        "conv_w": dense_init(r[1], (s.conv_kernel,
                                    din + 2 * s.n_groups * s.d_state),
                             jnp.float32, scale=0.5),
        "A_log": jnp.zeros((H,), jnp.float32),      # A = -exp(A_log) = -1
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm_w": jnp.zeros((din,), jnp.float32),
        "w_out": dense_init(r[2], (din, d), dt),
    }


def specs_mamba(cfg: ModelConfig, rules: Rules) -> dict:
    return {
        "w_in": rules.spec("data", "model"),
        "conv_w": P(),
        "A_log": P(), "D": P(), "dt_bias": P(),
        "norm_w": P(),
        "w_out": rules.spec("model", "data"),
    }


def _ssd_chunked(xh, dA, B, C, chunk, unroll=False):
    """SSD in chunked matmul form.
    xh: (b, s, H, P) already scaled by dt; dA: (b, s, H) = dt*A (<=0);
    B, C: (b, s, N) (n_groups=1).  Returns (y, final_state (b,H,N,P))."""
    b, s, H, Pd = xh.shape
    N = B.shape[-1]
    nc = s // chunk
    q = chunk
    xc = xh.reshape(b, nc, q, H, Pd)
    dAc = dA.reshape(b, nc, q, H)
    Bc = B.reshape(b, nc, q, N)
    Cc = C.reshape(b, nc, q, N)

    cums = jnp.cumsum(dAc, axis=2)                     # (b,nc,q,H)
    total = cums[:, :, -1]                             # (b,nc,H)

    # intra-chunk (diagonal blocks)
    cb = jnp.einsum("bcln,bcsn->bcls", Cc, Bc)         # (b,nc,q,q)
    seg = cums[:, :, :, None, :] - cums[:, :, None, :, :]   # (b,nc,q,q,H)
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    y_intra = jnp.einsum("bcls,bclsh,bcshp->bclhp", cb, decay, xc)

    # chunk boundary states: S_c = sum_s B_s x_s exp(total - cum_s)
    dec_out = jnp.exp(total[:, :, None, :] - cums)     # (b,nc,q,H)
    states = jnp.einsum("bcsn,bcsh,bcshp->bchnp", Bc, dec_out, xc)

    # inter-chunk recurrence over nc (sequential scan, nc is small)
    def step(h, xs):
        tot_c, st_c = xs
        h_new = h * jnp.exp(tot_c)[:, :, None, None] + st_c
        return h_new, h
    h0 = jnp.zeros((b, H, N, Pd), jnp.float32)
    hT, prev = jax.lax.scan(
        step, h0, (jnp.moveaxis(total, 1, 0).astype(jnp.float32),
                   jnp.moveaxis(states, 1, 0).astype(jnp.float32)),
        unroll=nc if unroll else 1)
    prev = jnp.moveaxis(prev, 0, 1)                    # state BEFORE chunk c

    y_inter = jnp.einsum("bcln,bchnp,bclh->bclhp", Cc, prev.astype(Cc.dtype),
                         jnp.exp(cums))
    y = (y_intra + y_inter).reshape(b, s, H, Pd)
    return y, hT


def mamba_block(p: dict, x: jax.Array, cfg: ModelConfig, rules: Rules,
                state: Optional[dict] = None, unroll: bool = False
                ) -> tuple[jax.Array, Optional[dict]]:
    """Mamba-2 block.  x: (B, S, D).  state (decode): {"conv", "ssm"}."""
    s_cfg = cfg.ssm
    b, s, d = x.shape
    din = s_cfg.expand * d
    H = din // s_cfg.head_dim
    N = s_cfg.n_groups * s_cfg.d_state
    Pd = s_cfg.head_dim

    zxbcdt = x @ p["w_in"]
    z, xb, Bm, Cm, dt = jnp.split(
        zxbcdt, [din, 2 * din, 2 * din + N, 2 * din + 2 * N], axis=-1)
    xbc = jnp.concatenate([xb, Bm, Cm], axis=-1)
    conv_state = state["conv"] if state is not None else None
    xbc, new_conv = causal_conv1d(xbc, p["conv_w"], conv_state)
    xb, Bm, Cm = jnp.split(xbc, [din, din + N], axis=-1)
    xb = constrain(xb, rules, "batch", None, "tp")

    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (b,s,H)
    A = -jnp.exp(p["A_log"])                                      # (H,)
    xr = xb.reshape(b, s, H, Pd).astype(jnp.float32)
    xh = xr * dtv[..., None]
    dA = dtv * A

    if state is None or s > 1:
        pad = (-s) % s_cfg.chunk
        if pad:
            xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))
            Bp = jnp.pad(Bm.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
            Cp = jnp.pad(Cm.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
        else:
            Bp, Cp = Bm.astype(jnp.float32), Cm.astype(jnp.float32)
        y, hT = _ssd_chunked(xh, dA, Bp, Cp, s_cfg.chunk, unroll=unroll)
        y = y[:, :s]
    else:
        h = state["ssm"]                                # (b,H,N,P)
        h = (h * jnp.exp(dA[:, 0])[:, :, None, None]
             + jnp.einsum("bn,bhp->bhnp", Bm[:, 0].astype(jnp.float32),
                          xh[:, 0]))
        y = jnp.einsum("bn,bhnp->bhp", Cm[:, 0].astype(jnp.float32),
                       h)[:, None]
        hT = h
    y = y + xr * p["D"][None, None, :, None]
    y = y.reshape(b, s, din)
    y = rmsnorm(y.astype(x.dtype), p["norm_w"], cfg.norm_eps)
    y = y * jax.nn.silu(z)
    out = y @ p["w_out"]
    new_state = {"conv": new_conv, "ssm": hT} if state is not None else None
    return constrain(out, rules, "batch", "seq", None), new_state


# ---------------------------------------------------------------------------
# RG-LRU (Griffin / RecurrentGemma, arXiv:2402.19427)
# ---------------------------------------------------------------------------

def init_rglru(rng, cfg: ModelConfig) -> dict:
    dt = _dtype(cfg)
    g = cfg.rglru
    d = cfg.d_model
    w = int(g.width_mult * d)
    r = jax.random.split(rng, 6)
    return {
        "w_gate_br": dense_init(r[0], (d, w), dt),   # gelu gate branch
        "w_main": dense_init(r[1], (d, w), dt),
        "conv_w": dense_init(r[2], (g.conv_kernel, w), jnp.float32, scale=0.5),
        "w_a": dense_init(r[3], (w, w), dt),         # recurrence gate
        "w_i": dense_init(r[4], (w, w), dt),         # input gate
        "lam": jnp.full((w,), 2.0, jnp.float32),     # a = sigmoid(lam)^(c*r)
        "w_out": dense_init(r[5], (w, d), dt),
    }


def specs_rglru(cfg: ModelConfig, rules: Rules) -> dict:
    return {
        "w_gate_br": rules.spec("data", "model"),
        "w_main": rules.spec("data", "model"),
        "conv_w": P(),
        "w_a": rules.spec("data", "model"),
        "w_i": rules.spec("data", "model"),
        "lam": P(),
        "w_out": rules.spec("model", "data"),
    }


def rglru_block(p: dict, x: jax.Array, cfg: ModelConfig, rules: Rules,
                state: Optional[dict] = None
                ) -> tuple[jax.Array, Optional[dict]]:
    """Griffin recurrent block: GeLU gate branch x (conv -> RG-LRU)."""
    g = cfg.rglru
    b, s, d = x.shape
    gate = jax.nn.gelu(x @ p["w_gate_br"])
    main = x @ p["w_main"]
    conv_state = state["conv"] if state is not None else None
    main, new_conv = causal_conv1d(main, p["conv_w"], conv_state)
    main = constrain(main, rules, "batch", None, "tp")

    r = jax.nn.sigmoid((main @ p["w_a"]).astype(jnp.float32))
    i = jax.nn.sigmoid((main @ p["w_i"]).astype(jnp.float32))
    log_a_base = jax.nn.log_sigmoid(p["lam"])          # (w,) < 0
    log_a = g.c_exponent * r * log_a_base              # (b,s,w)
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-9))
    bt = beta * i * main.astype(jnp.float32)

    if state is None or s > 1:
        def compose(e1, e2):
            a1, b1 = e1
            a2, b2 = e2
            return a2 * a1, a2 * b1 + b2
        a_sc, h = jax.lax.associative_scan(compose, (a, bt), axis=1)
        if state is not None:
            h0 = state["lru"][:, None]                 # (b,1,w)
            h = h + a_sc * h0
        hT = h[:, -1]
    else:
        h = a[:, 0] * state["lru"] + bt[:, 0]
        hT = h
        h = h[:, None]

    y = (gate.astype(jnp.float32) * h).astype(x.dtype)
    out = y @ p["w_out"]
    new_state = {"conv": new_conv, "lru": hT} if state is not None else None
    return constrain(out, rules, "batch", "seq", None), new_state


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder -> encoder output)
# ---------------------------------------------------------------------------

def init_cross_attention(rng, cfg: ModelConfig) -> dict:
    return init_attention(rng, cfg)


def specs_cross_attention(cfg: ModelConfig, rules: Rules) -> dict:
    return specs_attention(cfg, rules)


def cross_attention_block(p: dict, x: jax.Array, cfg: ModelConfig,
                          rules: Rules,
                          enc_out: Optional[jax.Array] = None,
                          kv_cache: Optional[dict] = None
                          ) -> tuple[jax.Array, dict]:
    """x: (B, S, D) decoder side; enc_out: (B, T, D) encoder output.
    kv_cache {"k","v"}: precomputed encoder projections (serving)."""
    b, s, d = x.shape
    dh = cfg.dh
    q = (x @ p["wq"]).reshape(b, s, cfg.n_heads, dh).transpose(0, 2, 1, 3)
    if kv_cache is None:
        t = enc_out.shape[1]
        k = (enc_out @ p["wk"]).reshape(b, t, cfg.n_kv_heads, dh)
        v = (enc_out @ p["wv"]).reshape(b, t, cfg.n_kv_heads, dh)
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        kv_cache = {"k": k, "v": v}
    k, v = kv_cache["k"], kv_cache["v"]
    group = cfg.n_heads // cfg.n_kv_heads
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    o = naive_attention(q, kk, vv, causal=False, window=0,
                        scale=1.0 / math.sqrt(dh))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * dh)
    out = o @ p["wo"]
    return constrain(out, rules, "batch", "seq", None), kv_cache
