"""Training driver — real execution on whatever devices exist.

Wires together: configs -> model -> optimizer -> data pipeline ->
fault-tolerant StepRunner (checkpoint/restart, straggler monitor).
On this CPU container it trains SMOKE (or --full) configs end-to-end;
the same code path drives the production mesh on TPU.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b \
        --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ALIASES, ARCHS, get_config
from ..data.pipeline import DataConfig, TokenPipeline
from ..dist.sharding import Rules
from ..models.lm import Runtime
from ..runtime.fault_tolerance import StepRunner
from . import steps as S
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b",
                    choices=sorted(ALIASES) + ARCHS)
    ap.add_argument("--full", action="store_true",
                    help="full config (default: smoke, CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8 error-feedback gradient reduction over "
                         "the data axis (dist.compression) — the "
                         "cross-pod DCI saver; needs --model-axis 1")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=not args.full)
    mesh = make_host_mesh(model_axis=args.model_axis)
    n_data = mesh.shape["data"]
    if args.compress_grads and args.model_axis != 1:
        ap.error("--compress-grads shard_maps the data reduction with "
                 "replicated params; tensor parallelism (--model-axis "
                 "> 1) is not supported on that path")
    rules = (Rules(data=("data",), model="model",
                   tp="model" if args.model_axis > 1 else None)
             if mesh.devices.size > 1 and not args.compress_grads
             else Rules.disabled())
    rt = Runtime(rules=rules,
                 mesh=mesh if mesh.devices.size > 1
                 and not args.compress_grads else None,
                 remat=False)
    model = S.build_model(cfg, rt)
    from ..optim.adamw import AdamW, cosine_schedule
    opt = AdamW(lr=cosine_schedule(args.lr,
                                   warmup=min(10, args.steps // 4 + 1),
                                   total=max(args.steps, 100)),
                clip_norm=1.0)

    params = model.init_params(jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"devices={mesh.devices.size}")

    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                    global_batch=args.batch, seed=args.seed))

    if args.compress_grads:
        print(f"gradient compression: int8+EF psum over data axis "
              f"({n_data} shard{'s' if n_data != 1 else ''})")
        train_step = jax.jit(
            S.make_compressed_train_step(model, opt, mesh),
            donate_argnums=(0, 1, 2))
    else:
        train_step = jax.jit(S.make_train_step(model, opt),
                             donate_argnums=(0, 1))

    def batch_for(step: int) -> dict:
        b = {k: jnp.asarray(v) for k, v in pipe.batch_at(step).items()}
        if cfg.family == "encdec":
            b["frames"] = jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(args.seed), step),
                (args.batch, cfg.encoder.n_frames, cfg.d_model),
                jnp.dtype(cfg.dtype))
        if cfg.n_prefix_embeds:
            b["prefix_embeds"] = jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(args.seed), step),
                (args.batch, cfg.n_prefix_embeds, cfg.d_model),
                jnp.dtype(cfg.dtype))
        return b

    losses = []

    def step_fn(state, batch):
        # state is (params, opt_state) or, with --compress-grads,
        # (params, opt_state, residuals) — both train_steps return
        # the new state leaves followed by the info dict
        out = train_step(*state, batch)
        info = out[-1]
        return tuple(out[:-1]), {"loss": float(info["loss"]),
                                 "grad_norm": float(info["grad_norm"])}

    def on_step(step, metrics):
        losses.append(metrics["loss"])
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {metrics['loss']:.4f} "
                  f"gnorm {metrics['grad_norm']:.3f} "
                  f"{metrics['step_time']*1e3:.0f}ms")

    state = (params, opt_state)
    if args.compress_grads:
        state = state + (S.init_grad_residuals(params, n_data),)
    if args.ckpt_dir:
        runner = StepRunner(step_fn=step_fn, batch_at=batch_for,
                            ckpt_dir=args.ckpt_dir,
                            ckpt_every=args.ckpt_every, on_step=on_step)
        state, log = runner.run(state, args.steps)
    else:
        for step in range(args.steps):
            t0 = time.perf_counter()
            state, m = step_fn(state, batch_for(step))
            m["step_time"] = time.perf_counter() - t0
            on_step(step, m)
    params, opt_state = state[0], state[1]

    print(f"final loss: {losses[-1]:.4f} (first: {losses[0]:.4f})")
    return {"first_loss": losses[0], "final_loss": losses[-1],
            "losses": losses}


if __name__ == "__main__":
    main()
