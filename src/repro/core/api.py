"""MCFuser public API: tune once, get a fused callable.

    from repro.core import api
    fn, report = api.fuse_gemm_chain(M=512, N=512, K=256, H=256, batch=1)
    e = fn(a, b, d)

Tuned schedules are cached at two levels so model code can call this at
trace time for every layer at zero cost after the first hit:

* per-process (``_CACHE``): (chain signature, hardware, mesh) ->
  TunedKernel — the paper's "tuning time" is paid once per shape;
* on disk (``core.schedule_cache``, ``REPRO_CACHE_DIR``): the search
  *outcome* survives process restarts, so a serving relaunch or a
  dry-run sweep cell re-tuning the same localized chain rebuilds the
  kernel in milliseconds without running ``heuristic_search`` at all.

The disk key uses ``MeshSpec.canonical()`` rather than the raw mesh:
two regimes that localize a chain identically and pay identical
collective terms (a 2x4 and a 4x2 mesh splitting the same loop 4-ways)
share one entry — identical localized chains tune once.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import codegen, pruning, schedule_cache
from .chain import Chain, attention_chain, gemm_chain, mlp_chain
from .dag import build_schedule
from .perf_model import (MeshSpec, TpuSpec, device_spec,
                         paged_gather_seconds)
from .search import SearchReport, heuristic_search, rank_regimes

_CACHE: dict[tuple, "TunedKernel"] = {}


@dataclass
class TunedKernel:
    fn: Callable
    report: SearchReport
    params: object
    tuning_seconds: float
    source: str = "search"   # "search" | "disk"

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _host_probe_due(rec: dict) -> bool:
    """True when a warm entry must be numerically probed before it is
    trusted: sentinels armed with probing on, and the record's stored
    host fingerprint differs from (or predates) the current host."""
    from ..reliability import sentinels as _sentinels
    spec = _sentinels.active()
    if spec is None or not spec.probe:
        return False
    return rec.get("host") != schedule_cache.host_fingerprint()


def _run_probe(kind: str, kernel_thunk, ref_thunk) -> bool:
    """One golden probe: canned input through the rebuilt kernel vs its
    XLA twin, per-dtype tolerance.  The ``wrong_answer`` fault seam
    (``op=f"probe-{kind}"``) perturbs the kernel side so the chaos
    suite can prove a corrupted replay is caught *before* traffic.
    A probe that raises counts as a mismatch — an entry that cannot
    even execute must not be trusted either."""
    from ..reliability import sentinels as _sentinels
    spec = _sentinels.active()
    try:
        got = _sentinels.corrupt_if_armed(kernel_thunk(),
                                          op=f"probe-{kind}")
        ok = bool(_sentinels.outputs_close(got, ref_thunk()))
    except Exception:  # noqa: BLE001 — unexecutable entry = mismatch
        ok = False
    if spec is not None:
        spec.note_probe(ok)
    return ok


def _pad_to(dim: int, tile: int) -> int:
    return int(math.ceil(dim / max(int(tile), 1)) * max(int(tile), 1))


def _probe_arrays(shapes: list[tuple], dtype: str) -> list[jax.Array]:
    """Deterministic canned probe operands (seeded, O(0.1) magnitude)."""
    rs = np.random.RandomState(0)
    return [jnp.asarray(rs.standard_normal(s) * 0.1, jnp.dtype(dtype))
            for s in shapes]


def _tune_or_load(kind: str, chain: Chain, hw: TpuSpec,
                  mesh: Optional[MeshSpec], unit: int, seed: int,
                  disk_key: tuple, measure_fn=None, probe_fn=None):
    """(report, params, seconds, source): disk-cache hit or full search.

    A hit rebuilds the winning Schedule through ``build_schedule`` and
    re-derives the kernel params, cross-checking them against the
    stored kwargs — a corrupt or semantically stale entry falls back to
    tuning instead of dispatching a bad kernel.  The rebuilt schedule
    is then re-validated against the pruning invariants
    (``pruning.validate_schedule``: Rules 2–4 + the VMEM bound) so a
    corrupted-but-parseable record never reaches Mosaic; a failing
    record is quarantined to ``.corrupt`` and retuned.

    ``probe_fn(params) -> bool`` is the sentinels' warm-load golden
    probe (docs/reliability.md): when the sentinels are armed and the
    record's stored host fingerprint differs from the current host
    (different jax version / backend / platform — the replay may lower
    differently than where it tuned), the entry must pass a numeric
    kernel-vs-twin probe before it is served.  Pass → the record is
    re-stamped with the current host (probes don't repeat every load);
    fail → the entry is quarantined and retuned.

    With a ``measure_fn`` (real-hardware wall-clock trials) the search
    outcome persists under the ``"measured"`` trial kind — a separate
    disk population from the default ``"analytic"`` one, so the two can
    never satisfy each other's lookups (measured entries embed hardware
    truth; analytic entries must not masquerade as it).
    """
    trial = "measured" if measure_fn is not None else "analytic"
    t0 = time.perf_counter()
    rec = schedule_cache.load(disk_key, hw, trial)
    if rec is not None:
        local = mesh.localize(chain) if mesh is not None else chain
        try:
            sched = build_schedule(local, rec["expr"], rec["tile_sizes"],
                                   hard_rule2=True)
            params = codegen.params_for(kind, sched)
            ok = sched.valid and params.as_kwargs() == rec["params"]
            if ok:
                ok, _why = pruning.validate_schedule(sched, hw, unit)
                if not ok:
                    # parsed and rebuilt but violates the pruning
                    # invariants: corrupt-but-parseable — keep the
                    # evidence, free the path for the retune
                    schedule_cache.quarantine_entry(disk_key, hw, trial)
        except Exception:  # noqa: BLE001 — any stale entry means retune
            ok = False
        if ok and probe_fn is not None and _host_probe_due(rec):
            if probe_fn(params):
                # probe passed on this host: re-stamp so subsequent
                # loads skip the probe until the host changes again
                schedule_cache.store(
                    disk_key, hw, expr=rec["expr"],
                    tile_sizes=rec["tile_sizes"],
                    best_time=rec["best_time"],
                    n_measured=rec["n_measured"],
                    n_iterations=rec["n_iterations"],
                    n_candidates=rec["n_candidates"],
                    prune_stats=rec["prune_stats"],
                    history=rec["history"], params=rec["params"],
                    trial=trial)
            else:
                schedule_cache.quarantine_entry(disk_key, hw, trial)
                ok = False
        if ok:
            report = SearchReport(
                best=sched, best_time=rec["best_time"],
                n_measured=rec["n_measured"],
                n_iterations=rec["n_iterations"],
                n_candidates=rec["n_candidates"],
                prune_stats=rec["prune_stats"],
                history=rec["history"], mesh=mesh)
            return report, params, time.perf_counter() - t0, "disk"

    report = heuristic_search(chain, measure_fn=measure_fn, hw=hw,
                              mesh=mesh, unit=unit, seed=seed)
    params = codegen.params_for(kind, report.best)
    dt = time.perf_counter() - t0
    schedule_cache.store(
        disk_key, hw, expr=report.best.expr,
        tile_sizes=report.best.tile_sizes, best_time=report.best_time,
        n_measured=report.n_measured, n_iterations=report.n_iterations,
        n_candidates=report.n_candidates, prune_stats=report.prune_stats,
        history=report.history, params=params.as_kwargs(), trial=trial)
    return report, params, dt, "search"


def fuse_gemm_chain(M: int, N: int, K: int, H: int, batch: int = 1,
                    dtype: str = "float32", hw: Optional[TpuSpec] = None,
                    mesh: Optional[MeshSpec] = None,
                    interpret: Optional[bool] = None,
                    unit: int = 128, seed: int = 0,
                    measure_fn=None) -> TunedKernel:
    """Tune and build the fused 2-GEMM-chain kernel E = (A@B)@D.

    (M, N, K, H, batch) are the GLOBAL problem dims; with a ``mesh`` the
    search localizes them and the returned kernel is parametrized for
    one shard's block (dispatch it under shard_map — ``kernels.ops``
    does this wiring).  ``measure_fn`` enables wall-clock trials (real
    TPU); its outcome caches under the distinct "measured" trial kind.
    """
    hw = hw or device_spec()
    interp = (not _is_tpu()) if interpret is None else interpret
    trial = "measured" if measure_fn is not None else "analytic"
    key = ("gemm", M, N, K, H, batch, dtype, hw.name, unit, mesh, interp,
           seed, trial)
    if key in _CACHE:
        return _CACHE[key]
    chain = gemm_chain(M, N, K, H, batch=batch, dtype=dtype)
    disk_key = ("gemm", M, N, K, H, batch, dtype, hw.name, unit,
                mesh.canonical() if mesh is not None else None, seed)

    def _probe(params) -> bool:
        # warm-load golden probe (sentinels): canned input, dims padded
        # to the entry's tiles, kernel vs the XLA reference twin
        from ..kernels import ref as _ref
        from ..kernels.gemm_chain import fused_gemm_chain as _k
        kw = params.as_kwargs()
        m, n = _pad_to(M, kw.get("bm", 1)), _pad_to(N, kw.get("bn", 1))
        k2, h = _pad_to(K, kw.get("bk", 1)), _pad_to(H, kw.get("bh", 1))
        a, b, d = _probe_arrays(
            [(batch, m, k2), (batch, k2, n), (batch, n, h)], dtype)
        return _run_probe(
            "gemm", lambda: _k(a, b, d, interpret=interp, **kw),
            lambda: _ref.gemm_chain_ref(a, b, d))

    report, params, dt, source = _tune_or_load(
        "gemm", chain, hw, mesh, unit, seed, disk_key,
        measure_fn=measure_fn,
        probe_fn=_probe if mesh is None else None)

    from ..kernels.gemm_chain import fused_gemm_chain as kernel

    fn = functools.partial(kernel, interpret=interp, **params.as_kwargs())
    tk = TunedKernel(fn, report, params, dt, source=source)
    _CACHE[key] = tk
    return tk


def fuse_mlp_chain(M: int, FF: int, D: int, batch: int = 1,
                   dtype: str = "float32", gated: bool = True,
                   act: str = "silu", hw: Optional[TpuSpec] = None,
                   mesh: Optional[MeshSpec] = None,
                   interpret: Optional[bool] = None,
                   unit: int = 128, seed: int = 0,
                   measure_fn=None) -> TunedKernel:
    """Tune and build the fused (gated) MLP chain kernel
    E = (act(A@Wg) * (A@Wu)) @ Wd — the chain ``core.planner`` carves
    for the memory-bound MLP half of a transformer block.

    (M, FF, D) are tokens, d_ff and d_model; the loop structure matches
    ``fuse_gemm_chain`` so the same schedule classes, pruning rules and
    cache machinery apply.  Entries persist under the distinct "mlp"
    key prefix, so they never collide with plain gemm-chain entries of
    the same dims.
    """
    hw = hw or device_spec()
    interp = (not _is_tpu()) if interpret is None else interpret
    trial = "measured" if measure_fn is not None else "analytic"
    key = ("mlp", M, FF, D, batch, gated, act, dtype, hw.name, unit,
           mesh, interp, seed, trial)
    if key in _CACHE:
        return _CACHE[key]
    chain = mlp_chain(M, FF, D, batch=batch, dtype=dtype, gated=gated,
                      act=act)
    disk_key = ("mlp", M, FF, D, batch, gated, act, dtype, hw.name, unit,
                mesh.canonical() if mesh is not None else None, seed)

    def _probe(params) -> bool:
        from ..kernels.gemm_chain import _ACTS as _acts
        from ..kernels.gemm_chain import fused_mlp_chain as _k
        kw = params.as_kwargs()
        m, n = _pad_to(M, kw.get("bm", 1)), _pad_to(FF, kw.get("bn", 1))
        k2, h = _pad_to(D, kw.get("bk", 1)), _pad_to(D, kw.get("bh", 1))
        shapes = [(batch, m, k2), (batch, k2, n), (batch, n, h)]
        if gated:
            shapes.append((batch, k2, n))
        arrs = _probe_arrays(shapes, dtype)
        a, wu, wd = arrs[:3]
        wg = arrs[3] if gated else None

        def _ref():
            hid = (_acts[act](a @ wg) * (a @ wu) if gated
                   else _acts[act](a @ wu))
            return hid @ wd

        return _run_probe(
            "mlp",
            lambda: _k(a, wu, wd, wg=wg, act=act, interpret=interp, **kw),
            _ref)

    report, params, dt, source = _tune_or_load(
        "mlp", chain, hw, mesh, unit, seed, disk_key,
        measure_fn=measure_fn,
        probe_fn=_probe if mesh is None else None)

    from ..kernels.gemm_chain import fused_mlp_chain as kernel

    fn = functools.partial(kernel, interpret=interp, act=act,
                           **params.as_kwargs())
    tk = TunedKernel(fn, report, params, dt, source=source)
    _CACHE[key] = tk
    return tk


def fuse_attention(M: int, N: int, K: int, H: int, heads: int = 1,
                   batch: int = 1, dtype: str = "float32",
                   causal: bool = False, window: int = 0,
                   scale: Optional[float] = None,
                   hw: Optional[TpuSpec] = None,
                   mesh: Optional[MeshSpec] = None,
                   interpret: Optional[bool] = None,
                   unit: int = 128, seed: int = 0,
                   measure_fn=None) -> TunedKernel:
    """Tune and build the fused attention kernel for (M, N, K, H).

    As with ``fuse_gemm_chain``, dims are global; a ``mesh`` tunes the
    per-shard block (heads/batch fold into the chain batch, so head and
    batch sharding enter through ``mesh.batch_axes`` — or, for the ring
    regime, the kv loop ``n`` enters through ``mesh.placement`` and the
    collective term prices the log-sum-exp combine).  ``measure_fn``
    enables wall-clock trials; see ``fuse_gemm_chain``."""
    hw = hw or device_spec()
    interp = (not _is_tpu()) if interpret is None else interpret
    trial = "measured" if measure_fn is not None else "analytic"
    key = ("attn", M, N, K, H, heads, batch, dtype, causal, window,
           scale, hw.name, unit, mesh, interp, seed, trial)
    if key in _CACHE:
        return _CACHE[key]
    chain = attention_chain(M, N, K, H, heads=heads, batch=batch,
                            dtype=dtype, causal=causal, window=window)
    disk_key = ("attn", M, N, K, H, heads, batch, dtype, causal, window,
                scale, hw.name, unit,
                mesh.canonical() if mesh is not None else None, seed)

    def _probe(params) -> bool:
        from ..kernels import ref as _ref
        from ..kernels.attention import fused_attention as _k
        kw = params.as_kwargs()
        m, n = _pad_to(M, kw.get("bq", 1)), _pad_to(N, kw.get("bkv", 1))
        q, k, v = _probe_arrays(
            [(batch, heads, m, K), (batch, heads, n, K),
             (batch, heads, n, H)], dtype)
        return _run_probe(
            "attn",
            lambda: _k(q, k, v, causal=causal, window=window,
                       scale=scale, interpret=interp, **kw),
            lambda: _ref.gqa_attention_ref(q, k, v, causal=causal,
                                           window=window, scale=scale))

    report, params, dt, source = _tune_or_load(
        "attn", chain, hw, mesh, unit, seed, disk_key,
        measure_fn=measure_fn,
        probe_fn=_probe if mesh is None else None)

    from ..kernels.attention import fused_attention as kernel

    fn = functools.partial(kernel, interpret=interp, causal=causal,
                           window=window, scale=scale, **params.as_kwargs())
    tk = TunedKernel(fn, report, params, dt, source=source)
    _CACHE[key] = tk
    return tk


def fuse_attention_paged(M: int, N: int, K: int, H: int, *,
                         page_size: int, heads: int = 1, batch: int = 1,
                         dtype: str = "float32", causal: bool = True,
                         window: int = 0, scale: Optional[float] = None,
                         hw: Optional[TpuSpec] = None,
                         mesh: Optional[MeshSpec] = None,
                         interpret: Optional[bool] = None,
                         unit: int = 128, seed: int = 0) -> TunedKernel:
    """Tune the attention chain for the paged-KV serving regime
    (docs/serving.md) and build ``kernels.attention.
    fused_attention_paged`` around the winning tiles.

    The tile search is the plain attention search — the paged-gather
    term is tile-independent — but both cache levels key the paged
    fingerprint ``("attn-paged", page_size)`` alongside
    ``MeshSpec.canonical()``, so paged entries never collide with the
    dense-attention population and a serving restart replays the
    regime decision from disk (``TunedKernel.source == "disk"``).
    ``report.best_time`` includes the paged-gather seconds
    (``perf_model.paged_gather_seconds`` on the localized chain), so
    ranking paged regimes compares eq (2') + gather like with like.
    Serving attention is causal by construction (``causal`` exists for
    pricing symmetry and must stay True for the built kernel).
    """
    hw = hw or device_spec()
    interp = (not _is_tpu()) if interpret is None else interpret
    key = ("attn-paged", page_size, M, N, K, H, heads, batch, dtype,
           causal, window, scale, hw.name, unit, mesh, interp, seed)
    if key in _CACHE:
        return _CACHE[key]
    chain = attention_chain(M, N, K, H, heads=heads, batch=batch,
                            dtype=dtype, causal=causal, window=window)
    disk_key = ("attn-paged", page_size, M, N, K, H, heads, batch, dtype,
                causal, window, scale, hw.name, unit,
                mesh.canonical() if mesh is not None else None, seed)
    # no numeric probe_fn: the paged entry is still schedule-validated
    # on every warm load, and the serving engine's construction-time
    # golden probe exercises the full paged decode against its twin
    # before traffic (serving/engine.py, docs/reliability.md)
    report, params, dt, source = _tune_or_load(
        "attn", chain, hw, mesh, unit, seed, disk_key)
    report = dataclasses.replace(
        report, best_time=report.best_time
        + paged_gather_seconds(chain, page_size, hw, mesh))

    from ..kernels.attention import fused_attention_paged as kernel

    fn = functools.partial(kernel, interpret=interp, window=window,
                           scale=scale, **params.as_kwargs())
    tk = TunedKernel(fn, report, params, dt, source=source)
    _CACHE[key] = tk
    return tk


@dataclass
class RegimeChoice:
    """Outcome of attention regime search: which parallelism regime the
    model ranks fastest for one global shape, plus every per-regime
    tuned kernel (all cached — losing regimes cost nothing to revisit
    when the shape recurs under a different mesh)."""

    regime: str
    kernel: TunedKernel
    times: dict[str, float]            # eq (2') best_time per regime
    kernels: dict[str, TunedKernel]


def fuse_attention_regimes(M: int, N: int, K: int, H: int, *,
                           heads: int = 1, batch: int = 1,
                           dtype: str = "float32", causal: bool = False,
                           window: int = 0, scale: Optional[float] = None,
                           hw: Optional[TpuSpec] = None,
                           regimes: dict[str, Optional[MeshSpec]],
                           interpret: Optional[bool] = None,
                           unit: int = 128, seed: int = 0) -> RegimeChoice:
    """Regime search (docs/design.md §7): tune the attention chain once
    per candidate ``MeshSpec`` and return the regime eq (2') ranks
    fastest.

    ``regimes`` maps a regime name to the MeshSpec the kernel would be
    dispatched under (``None`` = replicated single-device execution —
    still a regime, and the honest baseline when neither heads nor
    batch can cover the mesh).  Each tuning run goes through
    ``fuse_attention`` and therefore lands in both cache levels under
    its own ``MeshSpec.canonical()`` key; the cross-regime comparison
    is ``search.rank_regimes`` on the reported best times, which
    include the collective term — so the reduction-sharded (ring)
    regime only wins when its localized tile time plus the log-sum-exp
    combine's all-reduce beats the spatial regime's shard time.  List
    the collective-free regime first: ties break conservatively to it.
    """
    if not regimes:
        raise ValueError("regime search needs at least one candidate")
    kernels = {
        name: fuse_attention(M, N, K, H, heads=heads, batch=batch,
                             dtype=dtype, causal=causal, window=window,
                             scale=scale, hw=hw, mesh=spec,
                             interpret=interpret, unit=unit, seed=seed)
        for name, spec in regimes.items()
    }
    order = rank_regimes({n: tk.report for n, tk in kernels.items()})
    best = order[0]
    return RegimeChoice(
        regime=best, kernel=kernels[best],
        times={n: tk.report.best_time for n, tk in kernels.items()},
        kernels=kernels)


def fuse_attention_paged_regimes(M: int, N: int, K: int, H: int, *,
                                 page_size: int, heads: int = 1,
                                 batch: int = 1, dtype: str = "float32",
                                 window: int = 0,
                                 scale: Optional[float] = None,
                                 hw: Optional[TpuSpec] = None,
                                 regimes: dict[str, Optional[MeshSpec]],
                                 interpret: Optional[bool] = None,
                                 unit: int = 128,
                                 seed: int = 0) -> RegimeChoice:
    """Regime search over paged-attention candidates — the serving
    analogue of ``fuse_attention_regimes`` (docs/serving.md).  Every
    candidate is tuned through ``fuse_attention_paged`` (so its
    ``best_time`` carries eq (2') plus its own localized paged-gather
    term, and its outcome persists under the paged fingerprint), and
    the ranking is the same ``search.rank_regimes``.  List the
    collective-free regime ("paged-spatial") first: ties break to it.
    """
    if not regimes:
        raise ValueError("regime search needs at least one candidate")
    kernels = {
        name: fuse_attention_paged(M, N, K, H, page_size=page_size,
                                   heads=heads, batch=batch, dtype=dtype,
                                   causal=True, window=window,
                                   scale=scale, hw=hw, mesh=spec,
                                   interpret=interpret, unit=unit,
                                   seed=seed)
        for name, spec in regimes.items()
    }
    order = rank_regimes({n: tk.report for n, tk in kernels.items()})
    best = order[0]
    return RegimeChoice(
        regime=best, kernel=kernels[best],
        times={n: tk.report.best_time for n, tk in kernels.items()},
        kernels=kernels)


def clear_cache(disk: bool = False) -> None:
    """Drop the per-process cache; ``disk=True`` also wipes the
    persistent entries under ``REPRO_CACHE_DIR`` (tests)."""
    _CACHE.clear()
    if disk:
        schedule_cache.clear()
