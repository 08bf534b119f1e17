"""jit'd public wrappers for the fused kernels.

Backend dispatch: on TPU the Pallas kernel runs compiled; elsewhere
either the interpret-mode kernel (exact same body, Python-evaluated —
used by tests) or the XLA reference path (used by models during CPU
dry-runs, where Pallas cannot lower).  Padding for non-dividing tiles
happens here (Rule 3 keeps the overhead < 5%).

Sharded dispatch (docs/design.md §7): passing ``mesh=`` (plus optional
``dist.sharding.Rules``) wraps the kernel in ``jax.shard_map`` so
each shard runs the fused schedule on its local block — batch rides the
rules' data axes, the output-feature/head dim rides tp-or-model.  The
tuner is handed the matching ``MeshSpec``, so the tile sizes it picks
are for the per-shard sub-problem, not the global one.  Placements are
chosen collective-free (spatial dims only); dims the mesh cannot divide
evenly stay replicated rather than failing.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from ..core import api
from ..core.perf_model import MeshSpec
from ..dist import ring_dispatch
from ..dist.sharding import (Rules, batch_placement, default_rules,
                             dispatch_mesh_spec, feature_placement)
from . import ref
from .attention import fused_attention as _attn_kernel
from .gemm_chain import _ACTS
from .gemm_chain import fused_gemm_chain as _gemm_kernel
from .gemm_chain import fused_mlp_chain as _mlp_chain_kernel


def _backend_mode(mode: str) -> str:
    if mode != "auto":
        return mode
    return "kernel" if jax.default_backend() == "tpu" else "ref"


def _executed(out):
    """Wait for an eagerly dispatched kernel output, so a failure while
    it runs on the device raises here.  Inside a trace nothing runs:
    the output is returned as it is."""
    if any(isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(out)):
        return out
    return jax.block_until_ready(out)


def guarded(fingerprint: tuple, kernel_fn, ref_fn):
    """Tiered dispatch for a fused-kernel tail (docs/reliability.md).

    The breaker-open check routes a quarantined fingerprint straight to
    the XLA reference twin without retrying it.  Otherwise the fused
    path runs behind the ``kernel_dispatch`` fault point; an injected
    fault, or an error while the kernel executes, records the
    fingerprint (persisting a denylist record next to the cached
    schedule) before degrading to the twin.  Errors raised while the
    kernel is traced, lowered or compiled propagate: a kernel the
    compiler refuses is a bug, and degrading would hide that the fused
    path never ran.

    The tail is also a sentinel seam: ``wrong_answer`` faults perturb
    the fused output here, and when shadow verification is armed
    (``reliability/sentinels.py``) a sampled subset of dispatches is
    re-run on the twin and compared within per-dtype tolerance —
    a mismatch quarantines the fingerprint exactly like a crash, but
    the caller still receives the twin's correct output.
    """
    from ..reliability import breaker as _breaker
    from ..reliability import faults as _faults
    from ..reliability import sentinels as _sentinels
    op = str(fingerprint[0])
    if _breaker.is_open(fingerprint):
        return ref_fn()
    try:
        _faults.fault_point("kernel_dispatch", op=op)
        out = kernel_fn()
    except _faults.InjectedFault as e:
        _breaker.record_failure(fingerprint,
                                reason=f"{type(e).__name__}: {e}")
        return ref_fn()
    try:
        out = _executed(out)
    except jax.errors.JaxRuntimeError as e:
        _breaker.record_failure(fingerprint,
                                reason=f"{type(e).__name__}: {e}")
        return ref_fn()
    out = _sentinels.corrupt_if_armed(out, op=op)
    return _sentinels.shadow_kernel(fingerprint, out, ref_fn)


def gemm_chain(a: jax.Array, b: jax.Array, d: jax.Array,
               mode: str = "auto", tuned: bool = True,
               interpret: Optional[bool] = None,
               mesh: Optional[jax.sharding.Mesh] = None,
               rules: Optional[Rules] = None) -> jax.Array:
    """Fused E = (A@B)@D with MCFuser-tuned schedule.

    mode: "auto" | "kernel" | "interpret" | "ref".
    mesh: dispatch through shard_map — batch over the rules' data axes,
    H (d's last dim) over tp-or-model; the schedule is tuned for the
    local block.  rules defaults to the canonical data/model placement.
    """
    m = _backend_mode(mode)
    if m == "ref" and (mesh is None or a.ndim != 3):
        return ref.gemm_chain_ref(a, b, d)  # supports (..., M, K) batching
    bsz, M, K = a.shape
    N, H = b.shape[-1], d.shape[-1]
    interp = (m == "interpret") if interpret is None else interpret

    if mesh is not None:
        rules = rules if rules is not None else default_rules(mesh)
        spec, baxes, hax = dispatch_mesh_spec(
            rules, mesh, kind="gemm", batch=bsz, feature_dims=(H,))
        if baxes or hax:
            body = _gemm_body(M, N, K, H, bsz, str(a.dtype), m, tuned,
                              interp, spec)
            bspec = baxes if baxes else None
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(bspec, None, None), P(bspec, None, None),
                          P(bspec, None, hax)),
                out_specs=P(bspec, None, hax),
                check_vma=False)(a, b, d)
        # nothing shardable on this mesh: fall through to single-device

    if m == "ref":
        return ref.gemm_chain_ref(a, b, d)

    def _kernel():
        if tuned:
            tk = api.fuse_gemm_chain(M, N, K, H, batch=bsz,
                                     dtype=str(a.dtype), interpret=interp)
            return tk(a, b, d)
        return _gemm_kernel(a, b, d, interpret=interp)

    return guarded(("gemm", M, N, K, H, bsz, str(a.dtype)),
                   _kernel, lambda: ref.gemm_chain_ref(a, b, d))


def mlp_chain(x: jax.Array, w_up: jax.Array, w_down: jax.Array,
              w_gate: Optional[jax.Array] = None, act: str = "silu",
              mode: str = "auto", tuned: bool = True,
              interpret: Optional[bool] = None,
              prologue=None, epilogue=None,
              mesh: Optional[jax.sharding.Mesh] = None,
              rules: Optional[Rules] = None) -> jax.Array:
    """Fused E = (act(X@Wg) * (X@Wu)) @ Wd with MCFuser-tuned schedule
    (``w_gate=None`` computes the ungated E = act(X@Wu) @ Wd).

    x: (M, K); w_up/w_gate: (K, N); w_down: (N, H).  This is the
    planner executor's MLP dispatch point
    (``models/layers.run_planned_layer`` under
    ``Runtime(kernel_ops=True, planner=True)``): a planner-carved MLP
    chain executes the same ``gemm_chain.fused_mlp_chain`` schedule
    ``core.api.fuse_mlp_chain`` priced, instead of its XLA twin.

    mode: "auto" | "kernel" | "interpret" | "ref".  Ref mode is the
    exact XLA twin of ``models/layers.mlp_block``'s op sequence.
    ``prologue``/``epilogue`` are the tile-local FusionStitching hooks,
    forwarded to the kernel (applied whole-array in ref mode).

    mesh: dispatch through shard_map, tensor-parallel — tokens over the
    rules' data axes, d_ff over tp-or-model where it divides, each
    shard running the kernel tuned for its slice and the partial
    outputs summed over the axis (the epilogue then applies to the
    sum).  A Mosaic kernel cannot be partitioned by XLA, so on a mesh
    the kernel always runs under shard_map, replicated where nothing
    divides.
    """
    m = _backend_mode(mode)
    gated = w_gate is not None

    def _ref():
        h = x if prologue is None else prologue(x)
        if gated:
            hid = _ACTS[act](h @ w_gate) * (h @ w_up)
        else:
            hid = _ACTS[act](h @ w_up)
        e = hid @ w_down
        return e if epilogue is None else epilogue(e)

    if m == "ref":
        return _ref()
    if mesh is not None:
        rules = rules if rules is not None else default_rules(mesh)
        baxes = batch_placement(rules, mesh, x.shape[0])
        ax = feature_placement(rules, mesh, w_up.shape[-1], taken=baxes)
        bspec = baxes or None

        def body(xl, wul, wdl, *wgl):
            part = mlp_chain(xl, wul, wdl, w_gate=wgl[0] if wgl else None,
                             act=act, mode=m, tuned=tuned,
                             interpret=interpret, prologue=prologue)
            return part if ax is None else jax.lax.psum(part, ax)

        ws = P(None, ax)
        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(bspec, None), ws, P(ax, None)) + ((ws,) * gated),
            out_specs=P(bspec, None), check_vma=False)(
                x, w_up, w_down, *((w_gate,) * gated))
        return out if epilogue is None else epilogue(out)
    M, K = x.shape
    N, H = w_up.shape[-1], w_down.shape[-1]
    interp = (m == "interpret") if interpret is None else interpret

    def _kernel():
        kw = {}
        if tuned:
            tk = api.fuse_mlp_chain(M, N, H, batch=1, dtype=str(x.dtype),
                                    gated=gated, act=act,
                                    interpret=interp)
            kw = tk.params.as_kwargs()
        out = _mlp_chain_kernel(
            x[None], w_up[None], w_down[None],
            wg=w_gate[None] if gated else None, act=act,
            prologue=prologue, epilogue=epilogue, interpret=interp, **kw)
        return out[0]

    return guarded(("mlp", M, N, H, str(x.dtype), gated, act),
                   _kernel, _ref)


def _gemm_body(M, N, K, H, batch, dtype, m, tuned, interp,
               spec: MeshSpec):
    """Per-shard program: the tuned fused kernel on the local block.
    Tuning runs at trace time against the GLOBAL dims + MeshSpec, so
    the cached schedule is the localized one."""
    if m == "ref":
        return ref.gemm_chain_ref
    if tuned:
        tk = api.fuse_gemm_chain(M, N, K, H, batch=batch, dtype=dtype,
                                 mesh=spec, interpret=interp)
        return lambda al, bl, dl: tk(al, bl, dl)
    return functools.partial(_gemm_kernel, interpret=interp)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = False, window: int = 0,
              scale: Optional[float] = None,
              mode: str = "auto", tuned: bool = True,
              interpret: Optional[bool] = None,
              mesh: Optional[jax.sharding.Mesh] = None,
              rules: Optional[Rules] = None) -> jax.Array:
    """Fused GQA attention, MCFuser-tuned block schedule.

    q: (B, Hq, M, D), k/v: (B, Hkv, N, D/Dv).
    mesh: regime search + dispatch (docs/design.md §7).  Two regimes
    are enumerated through ``api.fuse_attention_regimes``:

    * spatial — shard_map with batch over the rules' data axes, heads
      over tp-or-model (kv heads must divide too, which preserves the
      GQA group per shard); collective-free.
    * ring — kv sequence sharded over tp-or-model, per-shard
      partial-softmax kernel + log-sum-exp combine
      (``dist.ring_dispatch``); pays the combine's all-reduce.
    * ring-pipelined — same sharding, but the combine runs as per-hop
      ``ppermute`` reduce-scatter + all-gather overlapped with tile
      compute (``MeshSpec(pipelined=True)``, eq 2' overlap term).

    The tuner prices all candidates under their ``MeshSpec`` (eq 2')
    and the cheapest is dispatched — for long kv contexts that a
    shard's batch/head slice cannot cover, that is one of the ring
    regimes (pipelined once compute is deep enough to hide the hops).
    """
    m = _backend_mode(mode)
    b, hq, M, D = q.shape
    hkv = k.shape[1]
    N, Dv = v.shape[-2], v.shape[-1]
    interp = (m == "interpret") if interpret is None else interpret

    if mesh is not None:
        rules = rules if rules is not None else default_rules(mesh)
        spec, baxes, hax = dispatch_mesh_spec(
            rules, mesh, kind="attention", batch=b,
            feature_dims=(hkv, hq))
        choice = None
        if m != "ref" and tuned:
            choice, plan = attention_regime_choice(
                rules, mesh, batch=b, q_heads=hq, kv_heads=hkv,
                q_len=M, kv_len=N, head_dim=D, v_dim=Dv,
                dtype=str(q.dtype), causal=causal, window=window,
                scale=scale, interpret=interp,
                spatial=(spec, baxes, hax))
        if choice is not None and choice.regime in ("ring",
                                                    "ring-pipelined"):
            p = choice.kernel.params
            return ring_dispatch.ring_attention(
                q, k, v, mesh=mesh, axis=plan.axis,
                batch_axes=plan.batch_axes, causal=causal,
                window=window, scale=scale, bq=p.bq, bkv=p.bkv,
                pipelined=(choice.regime == "ring-pipelined"),
                interpret=interp)
        if baxes or hax:
            body = _attn_body(M, N, D, Dv, hq, b, str(q.dtype), causal,
                              window, scale, m, tuned, interp, spec)
            bspec = baxes if baxes else None
            qs = P(bspec, hax, None, None)
            return jax.shard_map(
                body, mesh=mesh, in_specs=(qs, qs, qs), out_specs=qs,
                check_vma=False)(q, k, v)

    if m == "ref":
        return ref.gqa_attention_ref(q, k, v, causal=causal,
                                     window=window, scale=scale)

    def _kernel():
        if tuned:
            tk = api.fuse_attention(M, N, D, Dv, heads=hq, batch=b,
                                    dtype=str(q.dtype), causal=causal,
                                    window=window, scale=scale,
                                    interpret=interp)
            return tk(q, k, v)
        return _attn_kernel(q, k, v, causal=causal, window=window,
                            scale=scale, interpret=interp)

    return guarded(
        ("attn", M, N, D, Dv, hq, b, str(q.dtype), causal, window),
        _kernel,
        lambda: ref.gqa_attention_ref(q, k, v, causal=causal,
                                      window=window, scale=scale))


def _pipelined_rows_ok(plan, batch: int, q_heads: int, q_len: int) -> bool:
    """Whether the pipelined ring combine can run for this shape: the
    balanced reduce-scatter chunks the per-shard output rows
    ``(batch / batch_factor) * q_heads * q_len`` evenly across the ring
    — a row count the axis cannot divide stays serial rather than
    padding the wire."""
    n = plan.n_shards
    bf = plan.spec.batch_factor()
    if n < 2 or batch % bf:
        return False
    return (batch // bf) * q_heads * q_len % n == 0


def attention_regime_choice(rules: Rules, mesh: jax.sharding.Mesh, *,
                            batch: int, q_heads: int, kv_heads: int,
                            q_len: int, kv_len: int, head_dim: int,
                            v_dim: Optional[int] = None,
                            dtype: str = "float32",
                            causal: bool = False, window: int = 0,
                            scale: Optional[float] = None,
                            interpret: bool = True,
                            spatial=None):
    """(RegimeChoice, RingPlan) for one attention shape on this mesh —
    the exact decision ``attention()`` dispatches, factored out so
    tests, serving drivers, and the dry-run can ask "which regime would
    run here?" without executing anything.

    Returns ``(None, None)`` when the mesh offers no kv split (no ring
    candidate — the spatial path needs no search: it is the only
    option).  The spatial entry is the ``dispatch_mesh_spec`` placement
    when one exists, else ``None`` (replicated single-device
    execution), and is listed first so the collective-free regime wins
    ties.  ``spatial`` lets ``attention()`` pass the (spec, baxes,
    feature_axis) triple it already derived, so the regime compared
    here is the placement dispatched there by construction.
    """
    v_dim = head_dim if v_dim is None else v_dim
    if spatial is None:
        spatial = dispatch_mesh_spec(
            rules, mesh, kind="attention", batch=batch,
            feature_dims=(kv_heads, q_heads))
    spec, baxes, hax = spatial
    plan = ring_dispatch.plan_ring_attention(
        rules, mesh, batch=batch, kv_len=kv_len,
        feature_dims=(kv_heads, q_heads))
    if plan is None:
        return None, None
    regimes = {"spatial": spec if (baxes or hax) else None,
               "ring": plan.spec}
    if _pipelined_rows_ok(plan, batch, q_heads, q_len):
        regimes["ring-pipelined"] = dataclasses.replace(
            plan.spec, pipelined=True)
    choice = api.fuse_attention_regimes(
        q_len, kv_len, head_dim, v_dim, heads=q_heads, batch=batch,
        dtype=dtype, causal=causal, window=window, scale=scale,
        regimes=regimes, interpret=interpret)
    return choice, plan


def paged_attention_regime_choice(rules: Rules, mesh: jax.sharding.Mesh,
                                  *, batch: int, q_heads: int,
                                  kv_heads: int, q_len: int, kv_len: int,
                                  head_dim: int, page_size: int,
                                  v_dim: Optional[int] = None,
                                  dtype: str = "float32",
                                  window: int = 0,
                                  scale: Optional[float] = None,
                                  interpret: bool = True):
    """(RegimeChoice, RingPlan|None) for one PAGED decode shape — the
    serving twin of ``attention_regime_choice`` (docs/serving.md).

    Unlike the dense version this never returns ``(None, None)``: a
    mesh with no kv split still has the collective-free paged-spatial
    regime, and serving wants its TunedKernel (and its disk-cache
    provenance) either way.  Candidates:

    * paged-spatial — batch/heads over the mesh per
      ``dispatch_mesh_spec`` (or replicated when nothing divides);
      gathers the full page table per shard; collective-free.
    * paged-ring — page-table columns over tp-or-model
      (``dist.ring_dispatch.paged_ring_decode_attention``); each shard
      gathers only its slice of the pages, paying the partial-softmax
      combine.  Offered only when the axis splits ``kv_len`` at PAGE
      granularity — the dispatcher shards whole table columns, so a
      page count the axis cannot divide must not be priced as ring
      (the execution would silently fall back to the full gather).
    * paged-ring-pipelined — paged-ring with the per-hop ppermute
      combine (``MeshSpec(pipelined=True)``); offered when the decode
      rows also chunk evenly across the ring.

    All candidates are tuned through ``api.fuse_attention_paged`` so the ranking
    includes each regime's own localized paged-gather term and the
    outcomes persist under the paged cache fingerprint.
    """
    v_dim = head_dim if v_dim is None else v_dim
    spec, baxes, hax = dispatch_mesh_spec(
        rules, mesh, kind="attention", batch=batch,
        feature_dims=(kv_heads, q_heads))
    plan = ring_dispatch.plan_ring_attention(
        rules, mesh, batch=batch, kv_len=kv_len,
        feature_dims=(kv_heads, q_heads))
    if plan is not None and (kv_len % page_size
                             or (kv_len // page_size) % plan.n_shards):
        plan = None
    regimes = {"paged-spatial": spec if (baxes or hax) else None}
    if plan is not None:
        regimes["paged-ring"] = plan.spec
        if _pipelined_rows_ok(plan, batch, q_heads, q_len):
            regimes["paged-ring-pipelined"] = dataclasses.replace(
                plan.spec, pipelined=True)
    choice = api.fuse_attention_paged_regimes(
        q_len, kv_len, head_dim, v_dim, page_size=page_size,
        heads=q_heads, batch=batch, dtype=dtype, window=window,
        scale=scale, regimes=regimes, interpret=interpret)
    return choice, plan


def _attn_body(M, N, D, Dv, heads, batch, dtype, causal, window, scale,
               m, tuned, interp, spec: MeshSpec):
    if m == "ref":
        return functools.partial(ref.gqa_attention_ref, causal=causal,
                                 window=window, scale=scale)
    if tuned:
        tk = api.fuse_attention(M, N, D, Dv, heads=heads, batch=batch,
                                dtype=dtype, causal=causal,
                                window=window, scale=scale, mesh=spec,
                                interpret=interp)
        return lambda ql, kl, vl: tk(ql, kl, vl)
    return functools.partial(_attn_kernel, causal=causal, window=window,
                             scale=scale, interpret=interp)
