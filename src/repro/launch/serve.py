"""Serving driver: batched prefill + greedy decode over a KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
        --batch 4 --prompt-len 64 --gen 32

Two batching modes (docs/serving.md):

* **fixed** (default) — one batch, every request decodes in lock-step
  until the longest finishes; the baseline shape.
* **continuous** (``--continuous``) — the Orca-style
  ``serving.engine.ServingEngine`` over a paged KV cache: requests are
  admitted / prefilled / evicted per iteration on a ragged workload,
  so short requests never strand slot-steps behind long ones.

Sharded serving (regime-aware, docs/design.md §7): with
``--shard-model N`` the driver builds a host mesh whose model axis is
N, threads ``mesh=``/``rules=`` through the model Runtime — decode
attention then runs the distributed partial-softmax path over the
seq-sharded KV cache instead of silently using the unsharded path —
and prints the tuner's regime choice (spatial-vs-ring for fixed
batching; paged-spatial-vs-paged-ring for ``--continuous``).  Force
host devices first, e.g.::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --shard-model 4
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ALIASES, ARCHS, get_config
from ..dist.sharding import Rules
from ..models.lm import Runtime
from . import steps as S
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh


def generate(model, params, prompts: jax.Array, gen: int,
             frames=None, prefix_embeds=None) -> np.ndarray:
    """Greedy generation; prompts: (B, P) int32."""
    b, plen = prompts.shape
    extra = (frames.shape[1] if frames is not None else
             (prefix_embeds.shape[1] if prefix_embeds is not None else 0))
    cache = model.init_cache(b, plen + extra + gen)
    if frames is not None:
        logits, cache = jax.jit(model.prefill)(params, prompts, cache,
                                               frames)
    elif prefix_embeds is not None:
        logits, cache = jax.jit(model.prefill)(params, prompts, cache,
                                               prefix_embeds=prefix_embeds)
    else:
        logits, cache = jax.jit(model.prefill)(params, prompts, cache)
    decode = jax.jit(model.decode_step)
    out = [jnp.argmax(logits, -1)]
    pos = plen + extra
    for i in range(gen - 1):
        logits, cache = decode(params, cache, out[-1],
                               jnp.int32(pos + i))
        out.append(jnp.argmax(logits, -1))
    return np.stack([np.asarray(t) for t in out], axis=1)


def cut_layers(cfg, n_layers: int):
    """``cfg`` with its depth cut to ``n_layers``: every width stays
    published, only the stack is shorter.  The cut must hold whole
    periods of the layer pattern."""
    period = len(cfg.pattern)
    if not 0 < n_layers <= cfg.n_layers or n_layers % period:
        raise ValueError(f"--layers {n_layers}: need whole periods of "
                         f"{period} layer(s), at most {cfg.n_layers}")
    return dataclasses.replace(cfg, name=f"{cfg.name}-{n_layers}L",
                               n_layers=n_layers)


def sharded_runtime(shard_model: int, **runtime):
    """(mesh, rules, Runtime) for ``--shard-model N`` serving: N == 1
    is the plain single-device runtime; N > 1 builds the host mesh and
    the decode regime (resident TP weight shards, distributed
    partial-softmax decode over the seq-sharded KV cache).
    ``runtime`` sets further ``Runtime`` fields (``kernel_ops=True,
    planner=True`` for the fused path)."""
    if shard_model <= 1:
        return None, None, Runtime(remat=False, **runtime)
    mesh = make_host_mesh(model_axis=shard_model)
    rules = Rules(data=("data",), model="model", tp="model",
                  fsdp=False)   # decode regime: resident TP weights
    return mesh, rules, Runtime(rules=rules, mesh=mesh, remat=False,
                                dist_decode_attn=True, **runtime)


def demo_side_inputs(cfg, batch: int) -> tuple[dict, int]:
    """Random encoder frames / prefix embeds for archs that need them,
    plus the extra kv positions they prepend to the sequence."""
    kwargs: dict = {}
    extra = 0
    if cfg.family == "encdec":
        kwargs["frames"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (batch, cfg.encoder.n_frames, cfg.d_model))
        extra = cfg.encoder.n_frames
    if cfg.n_prefix_embeds:
        kwargs["prefix_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (batch, cfg.n_prefix_embeds, cfg.d_model))
        extra = cfg.n_prefix_embeds
    return kwargs, extra


def run_generate(cfg, model, params, prompts, gen: int, *,
                 mesh=None, rules=None, extra: int = 0,
                 **kwargs) -> tuple[np.ndarray, float]:
    """``generate`` wrapped for either posture; returns (tokens, s).

    With a mesh: enters it, prints the tuner's spatial-vs-ring regime
    choice for this job's attention shapes, and places the params
    before generating — the shared body of ``launch.serve`` and
    ``examples/serve_batched.py``."""
    b, plen = prompts.shape
    if mesh is None:
        t0 = time.perf_counter()
        tokens = generate(model, params, prompts, gen, **kwargs)
        return tokens, time.perf_counter() - t0
    with jax.set_mesh(mesh):
        report_attention_regimes(cfg, mesh, rules, batch=b,
                                 prompt_len=plen,
                                 total_len=plen + extra + gen)
        params = jax.device_put(
            params, S.shardings_for(mesh, model.param_specs()))
        t0 = time.perf_counter()
        tokens = generate(model, params, prompts, gen, **kwargs)
        return tokens, time.perf_counter() - t0


def report_attention_regimes(cfg, mesh, rules, *, batch: int,
                             prompt_len: int, total_len: int) -> dict:
    """Print (and return) the regime the tuner picks for this serving
    job's attention shapes — prefill (q=kv=prompt) and the grown
    decode context (q=prompt rows over the full kv) — via the exact
    decision path ``kernels.ops.attention`` dispatches."""
    from ..kernels import ops

    picks: dict[str, str] = {}
    for label, (m, n) in (("prefill", (prompt_len, prompt_len)),
                          ("decode_ctx", (prompt_len, total_len))):
        choice, _ = ops.attention_regime_choice(
            rules, mesh, batch=batch, q_heads=cfg.n_heads,
            kv_heads=cfg.n_kv_heads, q_len=m, kv_len=n,
            head_dim=cfg.dh, dtype=cfg.dtype, causal=True)
        if choice is None:
            picks[label] = "spatial"
            print(f"regime[{label}] q={m} kv={n}: spatial "
                  f"(mesh offers no kv split)")
        else:
            picks[label] = choice.regime
            times = " ".join(f"{k}={v * 1e6:.1f}us"
                             for k, v in choice.times.items())
            print(f"regime[{label}] q={m} kv={n}: {choice.regime} "
                  f"({times})")
    return picks


def ragged_workload(vocab: int, n_requests: int, prompt_len: int,
                    gen: int, seed: int = 0) -> list[tuple[np.ndarray, int]]:
    """Deterministic ragged serving workload: prompt lengths uniform in
    [prompt_len//2, prompt_len], generation budgets in [1, gen] — the
    divergence continuous batching exists to absorb."""
    rng = np.random.RandomState(seed)
    reqs = []
    for _ in range(n_requests):
        plen = int(rng.randint(max(1, prompt_len // 2), prompt_len + 1))
        g = int(rng.randint(1, gen + 1))
        reqs.append((rng.randint(0, vocab, size=plen).astype(np.int32), g))
    return reqs


def make_engine(model, params, *, batch: int, prompt_len: int, gen: int,
                page_size: int, verbose: bool = True):
    """A ``ServingEngine`` sized for ``batch`` concurrent requests of
    up to ``prompt_len + gen`` positions, with ~25% page slack so
    admission (prompt pages + one decode page of headroom) stays
    fluid without making preemption unreachable.  On a mesh the page
    count per sequence rounds up to a multiple of the model axis, so
    the paged-ring regimes, which split whole table columns, are
    offered to the tuner."""
    from ..serving import ServingEngine

    max_pages = math.ceil((prompt_len + gen) / page_size)
    rt = model.rt
    if rt.mesh is not None and rt.rules.model:
        n_model = rt.mesh.shape[rt.rules.model]
        max_pages = math.ceil(max_pages / n_model) * n_model
    n_pages = 1 + batch * (max_pages + 1) + max(1, batch * max_pages // 4)
    return ServingEngine(model, params, max_batch=batch,
                         page_size=page_size, n_pages=n_pages,
                         max_pages_per_seq=max_pages, verbose=verbose)


def run_continuous(cfg, model, params, *, batch: int, n_requests: int,
                   prompt_len: int, gen: int, page_size: int,
                   mesh=None, seed: int = 0, verbose: bool = True):
    """Continuous-batching serving of a ragged workload; returns
    (results, stats).  With a mesh: enters it, places the params, and
    lets the engine's tuner-priced regime choice decide whether decode
    attention runs paged-spatial or paged-ring (docs/serving.md)."""
    if cfg.family == "encdec" or cfg.n_prefix_embeds:
        raise NotImplementedError(
            f"--continuous covers decoder-only attention archs without "
            f"side inputs (docs/serving.md scope); {cfg.name} needs "
            f"encoder frames / prefix embeddings — serve it fixed-batch")
    reqs = ragged_workload(cfg.vocab, n_requests, prompt_len, gen, seed)
    ctx = jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with ctx:
        if mesh is not None:
            params = jax.device_put(
                params, S.shardings_for(mesh, model.param_specs()))
        engine = make_engine(model, params, batch=batch,
                             prompt_len=prompt_len, gen=gen,
                             page_size=page_size, verbose=verbose)
        results, stats = engine.run(reqs)
    return results, stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b",
                    choices=sorted(ALIASES) + ARCHS)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to N layers (whole periods of "
                         "the layer pattern); widths stay as configured")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shard-model", type=int, default=1,
                    help="model-axis size of the host mesh; > 1 serves "
                         "sharded (force host devices via XLA_FLAGS)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over a paged KV cache "
                         "(serving.engine) on a ragged workload")
    ap.add_argument("--requests", type=int, default=0,
                    help="ragged-workload size for --continuous "
                         "(default 4x batch)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size for --continuous")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=not args.full)
    if args.layers:
        cfg = cut_layers(cfg, args.layers)
        print(f"depth cut: {cfg.name} runs {cfg.n_layers} layers")
    mesh, rules, rt = sharded_runtime(args.shard_model)

    if args.continuous:
        model = S.build_model(cfg, rt)
        params = model.init_params(jax.random.PRNGKey(args.seed))
        n_requests = args.requests or 4 * args.batch
        results, stats = run_continuous(
            cfg, model, params, batch=args.batch, n_requests=n_requests,
            prompt_len=args.prompt_len, gen=args.gen,
            page_size=args.page_size, mesh=mesh, seed=args.seed + 1)
        shard = f" mesh=data{mesh.shape['data']}xmodel{mesh.shape['model']}" \
            if mesh is not None else ""
        counts = [len(r.tokens) for r in results]
        print(f"arch={cfg.name} continuous: {len(results)} requests, "
              f"{stats['generated']} tokens in {stats['wall_s']:.2f}s "
              f"({stats['tok_per_s']:.1f} tok/s) regime={stats['regime']} "
              f"steps={stats['decode_steps']} "
              f"preempt={stats['preemptions']}{shard}")
        print(f"per-request generated: {counts}")
        return results
    model = S.build_model(cfg, rt)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)
    kwargs, extra = demo_side_inputs(cfg, args.batch)
    tokens, dt = run_generate(cfg, model, params, prompts, args.gen,
                              mesh=mesh, rules=rules, extra=extra,
                              **kwargs)
    shard = f" mesh=data{mesh.shape['data']}xmodel{mesh.shape['model']}" \
        if mesh is not None else ""
    print(f"arch={cfg.name} generated {tokens.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s){shard}")
    print("sample:", tokens[0][:16].tolist())
    return tokens


if __name__ == "__main__":
    main()
