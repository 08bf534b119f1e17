"""Smoke run of the fused serving path on a TPU v5e.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded serving over four chips

Serves qwen3-8b at its published widths (``configs/qwen3_8b.py``
``FULL``), its depth cut to 4 layers (``launch.serve.cut_layers``), with
random bf16 weights from ``--seed``, through the engine that
``launch.serve.make_engine`` builds with the fused path on
(``Runtime(kernel_ops=True, planner=True)``): 8 requests, prompts of 128
and 256 tokens, 32 generated tokens each, 4 slots, 16-token pages.  The
same params and requests then run through the XLA twin (the default
``Runtime()``) in this process, and the two are compared.

``--chips 4`` runs only the sharded path: the same model on a
(data=1, model=4) mesh through ``launch.serve.sharded_runtime(4)``,
compared with the same requests served unsharded on one of the four
chips.

Every check prints one line; the run exits non-zero with the failed
check's reason unless all pass.  The seconds and bytes it prints are
those of a smoke run, not measurements.  The last line of its output is
one JSON object naming the device.  Without a TPU it exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
# this run's own schedule and quarantine cache, emptied at the start:
# nothing an earlier run left behind can steer what it tunes
SCHEDULES = ROOT / ".cache" / "chip_smoke_schedules"

N_LAYERS = 4
PROMPT_LENS = (128, 256)
N_REQUESTS = 8
GEN = 32
MAX_BATCH = 4
PAGE_SIZE = 16
# Prefill logits of the fused path and of the XLA twin must agree to
# LOGIT_TOL of the twin's largest logit magnitude.  The paths round to
# bf16 at different points (stitched glue and the fused MLP keep f32
# where the twin stores bf16 activations between ops).  One bf16
# rounding is at most 2**-8 relative; with about three such sites per
# layer over 4 layers the paths can drift 12 * 2**-8 ~ 4.7% apart in
# the worst case, so 5% bounds honest rounding and still catches a
# wrong kernel, whose error is of the order of the logits themselves.
LOGIT_TOL = 5e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def requests(vocab: int, seed: int):
    import numpy as np
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, vocab, size=PROMPT_LENS[i % 2]).astype(np.int32),
             GEN) for i in range(N_REQUESTS)]


def build(cfg, rt, seed: int):
    import jax
    from repro.launch import steps as S
    model = S.build_model(cfg, rt)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(seed))
    return model, params


def serve(model, params, reqs, label: str):
    """Build the engine through ``make_engine`` and run ``reqs``."""
    from repro.launch.serve import make_engine
    t0 = time.perf_counter()
    engine = make_engine(model, params, batch=MAX_BATCH,
                         prompt_len=max(PROMPT_LENS), gen=GEN,
                         page_size=PAGE_SIZE, verbose=True)
    setup_s = time.perf_counter() - t0
    results, stats = engine.run(reqs)
    print(f"[smoke, not a measurement] {label}: engine set-up "
          f"(regime tuning, decode pre-plan) {setup_s:.1f}s; lower and "
          f"compile of {len(engine.programs)} step programs (tuning of "
          f"planned chains included) {sum(engine.compile_s.values()):.1f}s;"
          f" serving {stats['wall_s']:.1f}s", flush=True)
    return engine, results, stats


def prefill_logits(engine, prompt):
    """Last-token prefill logits of ``prompt`` through the engine's own
    compiled prefill program, on a fresh cache."""
    import jax.numpy as jnp
    import numpy as np
    plen = len(prompt)
    n = -(-plen // engine.page_size)
    toks = np.zeros((1, n * engine.page_size), np.int32)
    toks[0, :plen] = prompt
    table = np.full((1, engine.max_pages), -1, np.int32)
    table[0, :n] = np.arange(1, n + 1)
    cache = engine.model.init_paged_cache(engine.pool.n_pages,
                                          engine.page_size)
    args = (engine.params, jnp.asarray(toks), cache, jnp.asarray(table),
            jnp.int32(plen))
    logits, _ = engine._program("prefill", args)(*args)
    return np.asarray(logits, np.float32)


def logits_by_prompt_len(engine, reqs) -> dict:
    return {plen: prefill_logits(engine, next(p for p, _ in reqs
                                              if len(p) == plen))
            for plen in PROMPT_LENS}


def compare_logits(got: dict, want: dict, label: str) -> None:
    import numpy as np
    for plen in PROMPT_LENS:
        a, b = got[plen], want[plen]
        err = float(np.max(np.abs(a - b)))
        scale = float(np.max(np.abs(b)))
        check(np.all(np.isfinite(a)) and err <= LOGIT_TOL * scale,
              f"{label} prefill logits, prompt {plen}: max|diff| {err:.4g}"
              f" <= {LOGIT_TOL} x max|ref| {scale:.4g}")


def token_agreement(res_a, res_b) -> str:
    same = sum(int(x == y) for ra, rb in zip(res_a, res_b)
               for x, y in zip(ra.tokens, rb.tokens))
    total = sum(len(r.tokens) for r in res_b)
    return f"{same}/{total}"


def check_served(engine, results, stats, label: str) -> None:
    from repro.core import schedule_cache
    from repro.reliability import breaker
    check(len(results) == N_REQUESTS
          and all(r.outcome == "complete" and len(r.tokens) == GEN
                  for r in results),
          f"{label}: all {N_REQUESTS} requests complete with {GEN} tokens")
    check(stats["exec_tier"] == "configured"
          and stats["tier_demotions"] == 0,
          f"{label}: exec_tier {stats['exec_tier']}, tier_demotions "
          f"{stats['tier_demotions']}")
    check(breaker.failures() == 0 and not schedule_cache.list_quarantined(),
          f"{label}: breaker failures {breaker.failures()}, quarantine "
          f"records {len(schedule_cache.list_quarantined())}")


def check_kernels(engine, cfg) -> None:
    """The configured tier's compiled step programs hold Mosaic kernels:
    every decode program, and every prefill program whose plan fused
    the MLP chain (a compute-bound prefill MLP is split by the planner
    and runs as plain matmuls, its attention as the XLA twin)."""
    from repro.core import planner
    for key, prog in sorted(engine.programs.items(), key=str):
        tier, phase = key[0], key[1]
        if tier != 0:
            continue
        n = prog.as_text().count("tpu_custom_call")
        if phase == "decode":
            want = True
            what = "decode"
        else:
            seq = key[2][0][1]     # padded prompt length (tokens shape)
            plan = planner.plan_model(
                cfg, 1, seq, stitch=engine.model.rt.stitch,
                phase="prefill", paged=engine.page_size,
                kv_len=engine.n_ctx)
            want = any(c.kind == "mlp" and c.fused
                       for c in plan.layer.chains)
            what = (f"prefill {seq} tokens (planner "
                    f"{'fused' if want else 'split'} the MLP)")
        check((n > 0) == want, f"{what}: {n} tpu_custom_call in the "
                               f"compiled program")


def one_chip(args, dev) -> None:
    import jax
    from repro.configs import get_config
    from repro.launch.serve import cut_layers, sharded_runtime
    from repro.models.lm import Runtime

    full = get_config("qwen3-8b")
    cfg = cut_layers(full, N_LAYERS)
    print(f"depth cut: {cfg.name} serves {cfg.n_layers} of "
          f"{full.n_layers} layers at published widths (d_model {cfg.d_model}, {cfg.n_heads}/"
          f"{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab})",
          flush=True)
    _, _, rt = sharded_runtime(1, kernel_ops=True, planner=True)
    model, params = build(cfg, rt, args.seed)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"[smoke] weights: {nbytes} bytes "
          f"({sum(x.size for x in jax.tree.leaves(params))} params)")
    reqs = requests(cfg.vocab, args.seed)

    fused, res_f, stats_f = serve(model, params, reqs, "fused")
    check_served(fused, res_f, stats_f, "fused")
    check_kernels(fused, cfg)
    twin_model = type(model)(cfg, Runtime())
    twin, res_t, _ = serve(twin_model, params, reqs, "xla twin")
    compare_logits(logits_by_prompt_len(fused, reqs),
                   logits_by_prompt_len(twin, reqs), "fused vs twin")
    print(f"greedy tokens equal, fused vs twin: "
          f"{token_agreement(res_f, res_t)}")
    print(f"[smoke] peak_bytes_in_use: {peak_bytes(dev)}", flush=True)


def four_chips(args, devices) -> None:
    import jax
    from repro.configs import get_config
    from repro.launch import steps as S
    from repro.launch.serve import cut_layers, sharded_runtime

    full = get_config("qwen3-8b")
    cfg = cut_layers(full, N_LAYERS)
    print(f"depth cut: {cfg.name} serves {cfg.n_layers} of "
          f"{full.n_layers} layers at published widths, sharded over {len(devices)} chips",
          flush=True)
    mesh, rules, rt = sharded_runtime(4, kernel_ops=True, planner=True)
    check(dict(mesh.shape) == {"data": 1, "model": 4},
          f"mesh {dict(mesh.shape)}")
    model, params = build(cfg, rt, args.seed)
    reqs = requests(cfg.vocab, args.seed)
    with jax.set_mesh(mesh):
        sharded_params = jax.device_put(
            params, S.shardings_for(mesh, model.param_specs()))
        leaf = max(jax.tree.leaves(sharded_params), key=lambda x: x.size)
        n_dev = len({s.device for s in leaf.addressable_shards})
        check(n_dev == 4 and leaf.addressable_shards[0].data.size
              < leaf.size,
              f"largest weight {leaf.shape} sharded over {n_dev} chips "
              f"({leaf.sharding.spec})")
        sharded, res_s, stats_s = serve(model, sharded_params, reqs,
                                        "sharded")
        print(f"regime pick: {sharded.regime} "
              f"({' '.join(f'{k}={v * 1e6:.1f}us' for k, v in sharded.regime_times.items())})")
        check_served(sharded, res_s, stats_s, "sharded")
        got = logits_by_prompt_len(sharded, reqs)
    _, _, rt1 = sharded_runtime(1, kernel_ops=True, planner=True)
    single_model = type(model)(cfg, rt1)
    single_params = jax.device_put(params, devices[0])
    single, res_1, _ = serve(single_model, single_params, reqs,
                             "one chip")
    compare_logits(got, logits_by_prompt_len(single, reqs),
                   "sharded vs one chip")
    print(f"greedy tokens equal, sharded vs one chip: "
          f"{token_agreement(res_s, res_1)}")
    for d in devices:
        print(f"[smoke] {d}: peak_bytes_in_use {peak_bytes(d)}")


def peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded serving path and its "
                         "one-chip reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    shutil.rmtree(SCHEDULES, ignore_errors=True)
    os.environ["REPRO_CACHE_DIR"] = str(SCHEDULES)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.core.perf_model import TPU_SPECS
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU found: JAX's first device is {dev.platform} "
              f"({dev.device_kind}); this smoke run needs the chip",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind!r}, "
          f"{len(devices)} attached", flush=True)
    print(f"compile cache: {enable_compile_cache()}")
    try:
        check(dev.device_kind in TPU_SPECS,
              f"the tuner's hardware table knows {dev.device_kind!r}")
        spec = TPU_SPECS[dev.device_kind]
        print(f"tuner prices against {spec.name}: VMEM budget "
              f"{spec.vmem_budget} of {spec.vmem_bytes} bytes")
        check(len(devices) >= args.chips,
              f"{args.chips} chip(s) requested, {len(devices)} attached")
        if args.chips == 1:
            one_chip(args, dev)
        else:
            four_chips(args, devices[:4])
    except SmokeFailure as e:
        print(f"smoke run failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
