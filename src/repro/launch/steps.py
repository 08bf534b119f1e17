"""Step builders + abstract input specs for every (arch x shape) cell.

`input_specs` returns ShapeDtypeStruct stand-ins (weak-type-correct,
shardable, no allocation) for the dry-run; `make_*_step` return the
jittable step callables used by both the dry-run and the real train /
serve drivers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ShapeCell
from ..dist.sharding import Rules, batch_placement
from ..models.config import ModelConfig
from ..models.lm import LM, Runtime
from ..models.whisper import EncDec
from ..optim.adamw import AdamW, cosine_schedule


def build_model(cfg: ModelConfig, rt: Optional[Runtime] = None):
    if cfg.family == "encdec":
        return EncDec(cfg, rt)
    return LM(cfg, rt)


def default_optimizer(total_steps: int = 10000) -> AdamW:
    return AdamW(lr=cosine_schedule(3e-4, warmup=200, total=total_steps))


def make_train_step(model, opt: AdamW):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params, opt_state, info = opt.update(params, grads, opt_state)
        info["loss"] = loss
        return params, opt_state, info
    return train_step


def init_grad_residuals(params, n_shards: int):
    """Zero error-feedback residuals: one f32 copy of every gradient
    leaf PER data shard, stacked on a leading ``n_shards`` axis (the
    axis ``make_compressed_train_step`` shards its residual state
    over)."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_shards,) + tuple(p.shape), jnp.float32),
        params)


def make_compressed_train_step(model, opt: AdamW,
                               mesh: jax.sharding.Mesh,
                               axis: str = "data"):
    """Train step with int8 error-feedback gradient reduction
    (``dist.compression.compressed_psum``) across the ``axis`` mesh
    dimension — the cross-pod reduction that rides the slow DCI links.

    The data-parallel reduction moves into an explicit ``shard_map``
    body: each shard takes ``value_and_grad`` over its local batch,
    quantizes ``grad + residual`` to int8, and psums the dequantized
    payload; the residual (per-shard state, leading ``n_shards`` axis)
    carries the quantization error into the next step, so the
    *transmitted sum* converges to the true sum (EF-SGD).  The
    optimizer runs outside the shard_map on the replicated reduced
    gradient, unchanged.

    Signature: ``(params, opt_state, residuals, batch) -> (params,
    opt_state, residuals, info)`` — one extra state leaf versus
    ``make_train_step``.  Params must be replicated across ``axis``
    (model-parallel sharding inside the body is not supported)."""
    from ..dist import compression
    n = mesh.shape[axis]

    def _body(params, batch, residuals):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        flat, treedef = jax.tree.flatten(grads)
        res = jax.tree.leaves(residuals)
        outs = [compression.compressed_psum(g, r[0], axis)
                for g, r in zip(flat, res)]
        # per-shard loss/grad are means over the LOCAL batch; psum/n
        # recovers the global-batch mean the uncompressed step computes
        grads = jax.tree.unflatten(
            treedef, [(o / n).astype(g.dtype)
                      for (o, _), g in zip(outs, flat)])
        new_res = jax.tree.unflatten(treedef, [r[None] for _, r in outs])
        loss = jax.lax.psum(loss, axis) / n
        return loss, grads, new_res

    reduce_grads = jax.shard_map(
        _body, mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=(P(), P(), P(axis)),
        check_vma=False)

    def train_step(params, opt_state, residuals, batch):
        loss, grads, residuals = reduce_grads(params, batch, residuals)
        params, opt_state, info = opt.update(params, grads, opt_state)
        info["loss"] = loss
        return params, opt_state, residuals, info
    return train_step


def make_prefill_step(model):
    def prefill_step(params, cache, batch):
        kwargs = {}
        if "frames" in batch:
            return model.prefill(params, batch["tokens"], cache,
                                 batch["frames"])
        if "prefix_embeds" in batch:
            return model.prefill(params, batch["tokens"], cache,
                                 prefix_embeds=batch["prefix_embeds"])
        return model.prefill(params, batch["tokens"], cache)
    return prefill_step


def make_decode_step(model):
    def decode_step(params, cache, batch):
        return model.decode_step(params, cache, batch["tokens"],
                                 batch["pos"])
    return decode_step


# ---------------------------------------------------------------------------
# Abstract inputs per shape cell
# ---------------------------------------------------------------------------

def _tok(shape) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def input_specs(cfg: ModelConfig, shape: ShapeCell) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.batch, shape.seq
    dt = jnp.dtype(cfg.dtype)
    if shape.kind == "train":
        batch: dict[str, Any] = {}
        if cfg.family == "encdec":
            batch["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder.n_frames, cfg.d_model), dt)
            batch["tokens"] = _tok((b, s))
            batch["labels"] = _tok((b, s))
        elif cfg.n_prefix_embeds:
            p = cfg.n_prefix_embeds
            batch["prefix_embeds"] = jax.ShapeDtypeStruct(
                (b, p, cfg.d_model), dt)
            batch["tokens"] = _tok((b, s - p))
            batch["labels"] = _tok((b, s - p))
        else:
            batch["tokens"] = _tok((b, s))
            batch["labels"] = _tok((b, s))
        return batch
    if shape.kind == "prefill":
        batch = {}
        if cfg.family == "encdec":
            batch["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder.n_frames, cfg.d_model), dt)
            batch["tokens"] = _tok((b, s))
        elif cfg.n_prefix_embeds:
            p = cfg.n_prefix_embeds
            batch["prefix_embeds"] = jax.ShapeDtypeStruct(
                (b, p, cfg.d_model), dt)
            batch["tokens"] = _tok((b, s - p))
        else:
            batch["tokens"] = _tok((b, s))
        return batch
    # decode: one new token against a cache of length `seq`
    return {"tokens": _tok((b,)),
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}


def batch_specs(cfg: ModelConfig, shape: ShapeCell, rules: Rules,
                mesh: jax.sharding.Mesh) -> dict:
    """PartitionSpecs matching input_specs."""
    b = shape.batch
    blead = batch_placement(rules, mesh, b) or None
    specs = {}
    for key in input_specs(cfg, shape):
        if key == "pos":
            specs[key] = P()
        elif key in ("frames", "prefix_embeds"):
            specs[key] = P(blead, None, None)
        elif key == "tokens" and shape.kind == "decode":
            specs[key] = P(blead)
        else:
            specs[key] = P(blead, None)
    return specs


def abstract_cache(model, cfg: ModelConfig, shape: ShapeCell):
    return jax.eval_shape(
        lambda: model.init_cache(shape.batch, shape.seq))


def shardings_for(mesh: jax.sharding.Mesh, specs):
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: isinstance(x, P))
