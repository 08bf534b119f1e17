"""Roofline-term extraction from compiled XLA artifacts (docs/design.md §6).

compute    = HLO_FLOPs_per_device / peak_FLOPs
memory     = HLO_bytes_per_device / HBM_bw
collective = estimated per-device link traffic / ICI_bw

cost_analysis() reports per-device flops / bytes on the forced-host
backend (verified in a pilot run).  collective traffic is parsed from
the optimized HLO: per op we apply the ring-algorithm traffic formulas
(core.ring — the same model core.perf_model prices collectives with
BEFORE compiling, so tuner and dry-run never disagree) to the result
shape and participant count.
"""
from __future__ import annotations

import dataclasses
import math
import re
from dataclasses import dataclass, field

from ..core.ring import ring_traffic_bytes

# v5e constants (also in core.perf_model.TpuSpec — duplicated here so the
# launch layer depends only on core.ring's pure arithmetic, not the tuner)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*((?:\([^)]*\))|(?:[\w\[\],{}\s]*?))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclass
class CollectiveStats:
    counts: dict = field(default_factory=dict)
    result_bytes: dict = field(default_factory=dict)
    traffic_bytes: float = 0.0      # per-device link traffic estimate

    def as_dict(self) -> dict:
        return {"counts": self.counts, "result_bytes": self.result_bytes,
                "traffic_bytes": self.traffic_bytes}


def parse_collectives(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        if "-done(" in line:
            continue  # start/done pairs: count the start only
        kind = m.group(3)
        rb = _shape_bytes(m.group(2))
        if rb == 0:
            continue
        g = _GROUPS_RE.search(line)
        if g:
            n = len(g.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            n = int(gi.group(2)) if gi else 2
        n = max(n, 2)
        traffic = ring_traffic_bytes(kind, rb, n)
        stats.counts[kind] = stats.counts.get(kind, 0) + 1
        stats.result_bytes[kind] = stats.result_bytes.get(kind, 0) + rb
        stats.traffic_bytes += traffic
    return stats


@dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_traffic: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_per_device: float = 0.0
    useful_ratio: float = 0.0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def roofline_terms(cost_analysis: dict, coll: CollectiveStats,
                   model_flops_per_device: float = 0.0,
                   peak_flops: float = PEAK_FLOPS, hbm_bw: float = HBM_BW,
                   ici_bw: float = ICI_BW) -> Roofline:
    flops = float(cost_analysis.get("flops", 0.0))
    byts = float(cost_analysis.get("bytes accessed", 0.0))
    compute_s = flops / peak_flops
    memory_s = byts / hbm_bw
    coll_s = coll.traffic_bytes / ici_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": coll_s}
    dominant = max(terms, key=terms.get)
    useful = (model_flops_per_device / flops) if flops else 0.0
    return Roofline(flops, byts, coll.traffic_bytes, compute_s, memory_s,
                    coll_s, dominant, model_flops_per_device, useful)


def model_flops(cfg, shape, n_devices: int) -> float:
    """MODEL_FLOPS per device: 6·N_active·D train, 2·N_active·D inference."""
    n_active = cfg.active_params()
    if shape.kind == "train":
        tokens = shape.batch * shape.seq
        total = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.batch * shape.seq
        total = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n_active * shape.batch
    return total / n_devices


def kernelized_attention_bytes(cfg, shape, n_dev: int, mesh=None,
                               rules=None,
                               regime_log: dict | None = None
                               ) -> tuple[float, int]:
    """Per-device HBM bytes of all attention layers when executed as the
    MCFuser-tuned fused Pallas kernel (score tiles stay in VMEM).

    Derived from the paper's analytical model (core.perf_model.t_mem) on
    the schedule picked by core.search for this exact (M, N, dh) — the
    tuner decides the production kernel's traffic, the dry-run only
    replaces XLA's unfusable-interior accounting with it.

    With a ``mesh`` (+ the cell's ``dist.sharding.Rules``), each layer
    shape runs the same **regime search** ``kernels.ops.attention``
    dispatches (docs/design.md §7): the spatial regime
    (``tuner_mesh_spec``, heads/batch over data + tp axes) against the
    ring regime (``shard_reduction=True``, kv sequence over tp) — the
    model picks per (q_len, kv_len), so long-context cells price the
    kv-sharded kernel exactly when serving would run it.  The returned
    bytes are one shard's traffic under the winning regime.  Meshless
    (mesh=None) keeps the legacy single-chip accounting: per-instance
    bytes times the ``batch * heads / n_dev`` head-batch fraction.

    ``regime_log`` (optional dict) records ``{"MxN": regime}`` per
    distinct layer shape for the sweep record.

    Returns (bytes, n_attention_instances).
    """
    from ..core import api
    from ..core.perf_model import t_mem, V5E

    if shape.kind == "decode":
        return 0.0, 0
    dh = cfg.dh
    s = shape.seq
    passes = 4.0 if shape.kind == "train" else 1.0  # fwd+remat+bwd(~2x)

    spec = None
    if mesh is not None:
        from .mesh import tuner_mesh_spec
        spec = tuner_mesh_spec(mesh, rules, kind="attention",
                               batch=shape.batch,
                               feature_dim=cfg.n_kv_heads)
        if spec.is_single:
            spec = None

    def layer_bytes(m, n):
        """Per-device bytes of one attention layer (all its local
        head-batch instances) for (q_len=m, kv_len=n)."""
        ring = None
        if mesh is not None:
            from .mesh import tuner_mesh_spec
            ring = tuner_mesh_spec(mesh, rules, kind="attention",
                                   batch=shape.batch,
                                   feature_dim=cfg.n_kv_heads,
                                   reduction_dim=n,
                                   shard_reduction=True)
            if not any(l == "n" for l, _ in ring.placement):
                ring = None   # no axis divides kv: not a ring regime
                # (a batch-only spec would just re-run the spatial
                # search under a second name)
        if spec is None and ring is None:
            tk = api.fuse_attention(m, n, dh, dh, heads=1, batch=1,
                                    dtype=cfg.dtype)
            hb = shape.batch * cfg.n_heads / n_dev
            return t_mem(tk.report.best, V5E) * V5E.hbm_bw * hb
        regimes = {"spatial": spec}
        if ring is not None:
            regimes["ring"] = ring
        choice = api.fuse_attention_regimes(
            m, n, dh, dh, heads=cfg.n_heads, batch=shape.batch,
            dtype=cfg.dtype, regimes=regimes)
        if regime_log is not None:
            regime_log[f"{m}x{n}"] = choice.regime
        if choice.regime == "spatial" and spec is None:
            # replicated spatial baseline won: keep the sweep's
            # per-device accounting (XLA still spreads the head-batch
            # instances across devices even though the fused dispatch
            # itself has nothing to shard); the kernel here was tuned
            # over the full head-batch, so divide by n_dev directly
            return t_mem(choice.kernel.report.best, V5E) * V5E.hbm_bw \
                / n_dev
        # t_mem of the localized chain already spans the shard's whole
        # head-batch (chain.batch localized by the spec's batch axes)
        return t_mem(choice.kernel.report.best, V5E) * V5E.hbm_bw

    total = 0.0
    count = 0
    if cfg.family == "encdec":
        t = cfg.encoder.n_frames
        t_pad = 128 * ((t + 127) // 128)
        total += layer_bytes(t_pad, t_pad) * cfg.encoder.n_layers
        total += layer_bytes(s, s) * cfg.n_layers          # dec self
        total += layer_bytes(s, t_pad) * cfg.n_layers      # cross
        count = cfg.encoder.n_layers + 2 * cfg.n_layers
    else:
        pat = list(cfg.pattern)
        n_attn = sum(1 for i in range(cfg.n_layers)
                     if pat[i % len(pat)] == "attn")
        if n_attn == 0:
            return 0.0, 0
        win = cfg.attn_window
        n_kv = min(s, win) if win else s
        total = layer_bytes(s, n_kv) * n_attn
        count = n_attn
    return total * passes, count


def planner_chain_report(cfg, shape, mesh=None, rules=None) -> dict:
    """Planner-carved chains for one dry-run cell (core/planner.py).

    Reports what the graph-level fusion planner would carve for this
    (config, shape) under the cell's tuner ``MeshSpec`` — which op
    groups stay fused MBCI chains, which split compute-bound, and
    where memory-bound glue got stitched — so a sweep record shows the
    planner's decisions next to the roofline they price into.  Plans
    replay from core.schedule_cache across cells.  Decode shape cells
    trace the ``phase="decode"`` DAG (one query row against a
    ``shape.seq``-long cache — the serving steady state, with its
    ``kv_write`` node standalone); other kinds trace the cache-free
    forward.  Non-plannable archs report ``{"plannable": False}``.
    """
    from ..core import planner

    if not planner.plannable(cfg):
        return {"plannable": False}
    spec = None
    if mesh is not None:
        from .mesh import tuner_mesh_spec
        spec = tuner_mesh_spec(mesh, rules, kind="attention",
                               batch=shape.batch,
                               feature_dim=cfg.n_kv_heads)
        if spec.is_single:
            spec = None
    if shape.kind == "decode":
        plan = planner.plan_model(cfg, shape.batch, 1, mesh=spec,
                                  phase="decode", kv_len=shape.seq)
    else:
        plan = planner.plan_model(cfg, shape.batch, shape.seq, mesh=spec)
    chains = [{
        "kind": c.kind, "ops": list(c.ops), "fused": c.fused,
        "ai": round(c.ai, 1),
        "prologue": list(c.prologue), "epilogue": list(c.epilogue),
    } for c in plan.layer.chains]
    return {
        "plannable": True,
        "phase": plan.phase,
        "ridge": round(planner.ridge_intensity(), 1),
        "chains": chains,
        "n_fused": sum(1 for c in plan.layer.chains if c.fused),
        "n_split": sum(1 for c in plan.layer.chains if not c.fused),
        "n_stitched": len(plan.layer.stitched()),
        "glue_standalone": list(plan.layer.glue),
        "stitches_dropped": list(plan.layer.dropped),
    }
