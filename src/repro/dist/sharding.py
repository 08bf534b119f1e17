"""Logical-axis sharding rules (the repo's partitioning DSL).

Model code describes tensors with *logical* axis names; a ``Rules``
instance maps them to physical mesh axes:

    weight specs (``Rules.spec``):
        "data"   -> the FSDP axes (``rules.data``); resolves to None
                    when ``fsdp=False`` (resident TP weights)
        "model"  -> the tensor/expert-parallel mesh axis
        "tp"     -> the activation tensor-parallel axis
        None     -> replicated

    activation constraints (``constrain``):
        "batch"  -> ``rules.batch_axes or rules.data`` (dropping axes
                    that do not divide the dimension)
        "seq"    -> ``rules.seq`` (sequence parallelism)
        "tp"     -> ``rules.tp``
        None     -> unconstrained

Why a DSL at all: FusionStitching-style global data-placement planning
only works when every layer states *intent* ("this dim is batch-like")
instead of hard-coding mesh axes — swapping the whole parallelism
regime (ZeRO-3 vs TP+SP vs TP, see launch/dryrun.py) is then a single
``Rules(...)`` literal, and the fused MCFuser kernels see consistently
placed operands on every regime.

Everything degrades to a no-op when rules are disabled or no mesh is
ambient, so single-device tests and the multi-pod dry-run share one
model implementation.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Union

import jax
from jax.sharding import PartitionSpec as P


AxisName = Union[str, Sequence[str], None]

_LOGICAL_AXES = (None, "batch", "seq", "tp", "model", "data")


def _as_tuple(axes: AxisName) -> tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


@dataclasses.dataclass(frozen=True)
class Rules:
    """Mapping from logical tensor axes to physical mesh axes.

    data:       mesh axes carrying data parallelism; also the FSDP
                weight-sharding axes while ``fsdp`` is True.
    model:      mesh axis for tensor/expert parallel weight shards.
    tp:         mesh axis for activation tensor parallelism (None in
                the ZeRO-3 regime: weights gather, activations stay
                replicated across the model axis).
    seq:        mesh axis for sequence parallelism on the residual
                stream (Megatron-SP), or None.
    batch_axes: override for batch-dim placement; defaults to ``data``
                (ZeRO-3 rides the batch over every axis).
    fsdp:       when False, "data" in weight specs resolves to None so
                TP weight shards stay resident (decode regime).
    """

    data: tuple[str, ...] = ()
    model: Optional[str] = None
    tp: Optional[str] = None
    seq: Optional[str] = None
    batch_axes: Optional[tuple[str, ...]] = None
    fsdp: bool = True

    @classmethod
    def disabled(cls) -> "Rules":
        """Rules under which every spec is fully replicated and
        ``constrain`` is the identity (single-device execution)."""
        return cls()

    @property
    def enabled(self) -> bool:
        return bool(self.data) or self.model is not None

    # ------------------------------------------------------------------
    # logical-axis resolution
    # ------------------------------------------------------------------
    def _resolve(self, name: Optional[str]) -> AxisName:
        if name is None:
            return None
        if name == "data":
            return (self.data or None) if self.fsdp else None
        if name == "model":
            return self.model
        if name == "tp":
            return self.tp
        if name == "seq":
            return self.seq
        if name == "batch":
            return tuple(self.batch_axes or self.data) or None
        raise ValueError(f"unknown logical axis {name!r}; expected one of "
                         f"{_LOGICAL_AXES}")

    def spec(self, *logical: Optional[str]) -> P:
        """PartitionSpec for a weight whose dims carry the given logical
        axes.  ``rules.spec("data", "model")`` on a (D, F) projection
        FSDP-shards D and tensor-shards F; disabled rules replicate."""
        if not self.enabled:
            return P(*(None,) * len(logical))
        return P(*(self._resolve(name) for name in logical))

    def batch_spec(self, batch: int, mesh: Optional[jax.sharding.Mesh]) -> P:
        """Placement of a leading batch dimension of size ``batch``.

        Returns a length-1 PartitionSpec over the mesh axes the batch
        dim shards over, or an empty spec when the batch cannot be
        sharded.  jax normalises a one-axis tuple entry to the bare
        axis name, so callers that need the axes themselves take the
        tuple from ``batch_placement``.  Degrades gracefully: axes are dropped
        from the right until their combined size divides ``batch``, so
        a batch of 4 on a (data=2, model=4) mesh still shards over
        data instead of failing.
        """
        axes = batch_placement(self, mesh, batch)
        return P(axes) if axes else P()


def _divisible_axes(rules: Rules, mesh, name: Optional[str],
                    dim: int) -> tuple[str, ...]:
    """Mesh axes for one tensor dim, dropping axes (from the right)
    that the dim's size cannot absorb evenly — keeps placements valid
    on smoke-sized tensors and partially-covering batches."""
    axes = tuple(a for a in _as_tuple(rules._resolve(name))
                 if a in mesh.shape and mesh.shape[a] > 1)
    while axes and dim % math.prod(mesh.shape[a] for a in axes):
        axes = axes[:-1]
    return axes


def _dim_axes(rules: Rules, mesh: jax.sharding.Mesh,
              name: Optional[str], dim: int) -> AxisName:
    axes = _divisible_axes(rules, mesh, name, dim)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def default_rules(mesh: jax.sharding.Mesh) -> Rules:
    """Canonical placements when a caller has a mesh but no Rules:
    every pod/data axis carries batch, a model axis carries features."""
    names = tuple(mesh.shape)
    data = tuple(a for a in names if a in ("pod", "data"))
    model = "model" if "model" in names else None
    return Rules(data=data, model=model, tp=model)


def batch_placement(rules: Rules, mesh: jax.sharding.Mesh,
                    batch: int) -> tuple[str, ...]:
    """Data axes a batch dim of size ``batch`` shards over, always as a
    tuple (dropping non-dividing axes; ``()`` when the batch stays
    whole).  Shared by the kernel dispatcher (``kernels.ops``), the
    tuner bridge (``launch.mesh.tuner_mesh_spec``) and every cache and
    input spec, so the tuner prices exactly what is dispatched."""
    if not rules.enabled or mesh is None:
        return ()
    return _divisible_axes(rules, mesh, "batch", batch)


def feature_placement(rules: Rules, mesh: jax.sharding.Mesh,
                      dim: int,
                      taken: tuple[str, ...] = ()) -> Optional[str]:
    """The tp-or-model axis, if it evenly divides ``dim``.

    ``taken`` excludes axes already consumed by the batch placement —
    the ZeRO-3 regime routes the model axis through ``batch_axes``
    (batch rides every axis), and a mesh axis may appear only once in
    a PartitionSpec."""
    ax = rules.tp or rules.model
    if ax and ax not in taken and ax in mesh.shape \
            and mesh.shape[ax] > 1 and dim % mesh.shape[ax] == 0:
        return ax
    return None


def dispatch_mesh_spec(rules: Rules, mesh: jax.sharding.Mesh, *,
                       kind: str, batch: int,
                       feature_dims: tuple[int, ...],
                       ici_bw: Optional[float] = None):
    """(MeshSpec, batch_axes, feature_axis) for dispatching one fused
    kernel under this mesh + regime — THE single builder both the
    kernel dispatcher (``kernels.ops``) and the tuner bridge
    (``launch.mesh.tuner_mesh_spec``) call, so the tuner can never
    price a regime the dispatcher would not run.

    kind "gemm": the feature axis splits the ``h`` loop (output
    features) as a MeshSpec placement entry; ``feature_dims=(H,)``.
    kind "attention": heads fold into the *chain batch*
    (``attention_chain`` batch = model batch x heads), so the feature
    axis joins ``batch_axes`` and no loop is placed;
    ``feature_dims=(kv_heads, q_heads)`` — the axis must divide every
    entry, which also preserves the GQA group per shard.
    """
    from ..core.perf_model import MeshSpec, V5E
    if kind not in ("gemm", "attention"):
        raise ValueError(f"unknown chain kind {kind!r}")
    baxes = batch_placement(rules, mesh, batch)
    feat = (feature_placement(rules, mesh, feature_dims[0], taken=baxes)
            if feature_dims else None)
    if feat is not None and any(d % mesh.shape[feat]
                                for d in feature_dims[1:]):
        feat = None
    ici_bw = V5E.ici_bw if ici_bw is None else ici_bw
    if kind == "attention":
        spec = MeshSpec.from_mesh(
            mesh, batch_axes=baxes + ((feat,) if feat else ()),
            ici_bw=ici_bw)
    else:
        spec = MeshSpec.from_mesh(
            mesh, placement=((("h", feat),) if feat else ()),
            batch_axes=baxes, ici_bw=ici_bw)
    return spec, baxes, feat


def ring_dispatch_spec(rules: Rules, mesh: jax.sharding.Mesh, *,
                       batch: int, kv_len: int,
                       feature_dims: tuple[int, ...] = (),
                       ici_bw: Optional[float] = None):
    """(MeshSpec, batch_axes, reduction_axis) for the ring
    (kv-sequence-sharded) attention regime — the reduction-sharding
    sibling of ``dispatch_mesh_spec``, and like it THE single builder
    both the dispatcher (``dist.ring_dispatch`` via ``kernels.ops``)
    and the tuner bridge (``launch.mesh.tuner_mesh_spec(
    shard_reduction=True)``) call, so the priced regime and the
    executed regime can never drift apart.

    The batch keeps riding the rules' data axes; the tp-or-model axis
    splits the chain's ``n`` loop (the kv sequence — the cross-op
    reduction of the attention chain) instead of the heads.  Gating is
    by ``kv_len`` divisibility; ``feature_dims`` is unused for the
    placement but accepted for signature symmetry.  Returns a
    reduction_axis of None (and a spatial-only MeshSpec) when the mesh
    offers no axis that divides ``kv_len``.
    """
    from ..core.perf_model import MeshSpec, V5E
    baxes = batch_placement(rules, mesh, batch)
    ax = rules.tp or rules.model
    if not (ax and ax not in baxes and ax in mesh.shape
            and mesh.shape[ax] > 1 and kv_len % mesh.shape[ax] == 0):
        ax = None
    ici_bw = V5E.ici_bw if ici_bw is None else ici_bw
    spec = MeshSpec.from_mesh(
        mesh, placement=((("n", ax),) if ax else ()),
        batch_axes=baxes, ici_bw=ici_bw)
    return spec, baxes, ax


def constrain(x: jax.Array, rules: Rules,
              *logical: Optional[str]) -> jax.Array:
    """Apply ``jax.lax.with_sharding_constraint`` mapping each of ``x``'s
    dims through the rules' logical-axis table.

    No-op when rules are disabled or no mesh is ambient (set via
    ``jax.set_mesh``), so the same model code traces unchanged on a
    single device.  Logical names beyond ``x.ndim`` are ignored;
    unnamed trailing dims are unconstrained.
    """
    if rules is None or not rules.enabled:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    entries = [_dim_axes(rules, mesh, name, dim)
               for dim, name in zip(x.shape, logical)]
    entries += [None] * (x.ndim - len(entries))
    return jax.lax.with_sharding_constraint(x, P(*entries))
