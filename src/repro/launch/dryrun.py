"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
    with mesh:
        lowered  = jax.jit(step, in_shardings=..., out_shardings=...) \
                       .lower(**input_specs(arch))
        compiled = lowered.compile()
        memory_analysis / cost_analysis / collective parse

Results are written incrementally to --out (JSON per cell) so the full
sweep is resumable; failures are recorded, not swallowed.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
        --shape train_4k --mesh single          # one cell
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

``main`` forces 512 host devices through ``XLA_FLAGS`` before JAX first
touches a backend; importing this module changes nothing.
"""
import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ALIASES, ARCHS, SHAPES, cell_applicable, get_config
from ..dist.sharding import Rules
from ..models.lm import Runtime
from . import hlo_analysis, hlo_cost, steps
from .mesh import make_production_mesh


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             remat_policy: str = "full", regime: str = "auto",
             dist_decode: bool = False,
             extra: dict | None = None) -> dict:
    """Lower+compile one cell; returns the analysis record."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    dp = ("pod", "data") if multi_pod else ("data",)
    # Parallelism regime per cell kind (docs/design.md §5):
    #  * dense/ssm/hybrid train: ZeRO-3 — batch over every axis, params
    #    2-D sharded and gathered per layer; no activation TP collectives.
    #    (multi-pod keeps the pod axis on batch and adds SP since batch
    #    256 cannot cover 512 chips.)
    #  * MoE train + all prefill: TP(+EP) over model, Megatron-SP on the
    #    residual stream.
    #  * decode: TP with resident weight shards; no SP (S == 1).
    if regime == "auto":
        regime = "tp" if shape.kind == "decode" else "tp+sp"
    if regime == "zero3":
        # collective-light variant (SS Perf): batch over every axis,
        # params gathered per layer; single-pod only — at 512 chips the
        # 256-seq global batch cannot cover the mesh.
        rules = Rules(data=dp, model="model",
                      batch_axes=dp + (("model",) if not multi_pod else ()),
                      tp=None, seq="model" if multi_pod else None)
    elif regime == "tp":
        rules = Rules(data=dp, model="model", tp="model", seq=None,
                      fsdp=not dist_decode)  # it2: resident TP weights
    else:
        rules = Rules(data=dp, model="model", tp="model", seq="model")
    rt = Runtime(rules=rules, mesh=mesh,
                 remat=(shape.kind == "train" and remat_policy != "none"),
                 remat_policy=("dots" if remat_policy == "dots" else None),
                 dist_decode_attn=dist_decode,
                 bkv=2048 if shape.kind == "prefill" else 512)
    model = steps.build_model(cfg, rt)

    t0 = time.perf_counter()
    a_params = model.abstract_params()
    p_specs = model.param_specs()
    p_sh = steps.shardings_for(mesh, p_specs)
    b_abs = steps.input_specs(cfg, shape)
    b_specs = steps.batch_specs(cfg, shape, rules, mesh)
    b_sh = steps.shardings_for(mesh, b_specs)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = steps.default_optimizer()
            a_opt = opt.abstract_state(a_params)
            o_specs = opt.state_specs(p_specs)
            o_sh = steps.shardings_for(mesh, o_specs)
            fn = steps.make_train_step(model, opt)
            jitted = jax.jit(
                fn, in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(a_params, a_opt, b_abs)
        else:
            a_cache = steps.abstract_cache(model, cfg, shape)
            c_specs = model.cache_specs(shape.batch)
            c_sh = steps.shardings_for(mesh, c_specs)
            fn = (steps.make_prefill_step(model) if shape.kind == "prefill"
                  else steps.make_decode_step(model))
            jitted = jax.jit(
                fn, in_shardings=(p_sh, c_sh, b_sh),
                out_shardings=(None, c_sh), donate_argnums=(1,))
            lowered = jitted.lower(a_params, a_cache, b_abs)
        t_lower = time.perf_counter() - t0

        t1 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t1

    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    cost_model = hlo_cost.HloCostModel(hlo)
    attr = hlo_cost.AttributedCost(cost_model)
    total = hlo_cost.Cost()
    total.add(attr.attn)
    total.add(attr.rest)

    mf = hlo_analysis.model_flops(cfg, shape, n_dev)
    # MCFuser kernelization: replace XLA's unfusable attention-interior
    # HBM traffic by the tuned fused-kernel traffic (the paper's win),
    # regime-searched under THIS cell's mesh (spatial vs ring per layer
    # shape, the same decision kernels.ops.attention dispatches) — and
    # cached on disk (core.schedule_cache), so identical localized
    # chains across sweep cells tune once.
    attn_regimes: dict = {}
    attn_kernel_bytes, n_attn = hlo_analysis.kernelized_attention_bytes(
        cfg, shape, n_dev, mesh=mesh, rules=rules,
        regime_log=attn_regimes)
    bytes_xla = total.bytes
    if shape.kind == "decode":
        # single-token decode has no fusable attention interior, and the
        # inline attention dot would mis-attribute the whole layer body
        bytes_kernelized = bytes_xla
    else:
        bytes_kernelized = attr.rest.bytes + min(attn_kernel_bytes,
                                                 attr.attn.bytes)

    compute_s = total.flops / hlo_analysis.PEAK_FLOPS
    memory_s = bytes_kernelized / hlo_analysis.HBM_BW
    memory_s_xla = bytes_xla / hlo_analysis.HBM_BW
    collective_s = total.coll_traffic / hlo_analysis.ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev, "regime": regime,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_per_device_gb": round(
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30,
                3),
        },
        "collectives": {"counts": {k: round(v, 1) for k, v
                                   in total.coll_counts.items()},
                        "result_bytes": {k: round(v, 1) for k, v
                                         in total.coll_bytes.items()},
                        "traffic_bytes": total.coll_traffic},
        "attention": {
            "interior_bytes_xla": attr.attn.bytes,
            "kernelized_bytes": attn_kernel_bytes,
            "n_instances": n_attn,
            "regimes": attn_regimes,   # {"MxN": "spatial" | "ring"}
        },
        # graph-level fusion planner's carve/stitch decisions for this
        # cell (core/planner.py; {"plannable": False} when the arch or
        # shape is outside the planner's domain)
        "planner": hlo_analysis.planner_chain_report(
            cfg, shape, mesh=mesh, rules=rules),
        "roofline": {
            "flops_per_device": total.flops,
            "bytes_per_device": bytes_kernelized,
            "bytes_per_device_xla": bytes_xla,
            "collective_traffic": total.coll_traffic,
            "compute_s": compute_s,
            "memory_s": memory_s,
            "memory_s_xla": memory_s_xla,
            "collective_s": collective_s,
            "dominant": dominant,
            "model_flops_per_device": mf,
            "useful_ratio": mf / total.flops if total.flops else 0.0,
        },
    }
    if extra:
        rec.update(extra)
    return rec


def main() -> None:
    # must precede the first backend use: jax fixes the device count then
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ALIASES) + ARCHS)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", choices=("full", "dots", "none"),
                    default="full")
    ap.add_argument("--regime", choices=("auto", "zero3", "tp+sp", "tp"),
                    default="auto")
    ap.add_argument("--dist-decode", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true",
                    help="recompute cells that already have a JSON")
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    archs = ARCHS if args.all or not args.arch else [
        ALIASES.get(args.arch, args.arch)]
    shapes = sorted(SHAPES) if args.all or not args.shape else [args.shape]
    for a in archs:
        for s in shapes:
            cells.append((a, s))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for arch, shape in cells:
        for multi in meshes:
            tag = f"{arch}__{shape}__{'multi' if multi else 'single'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path) and not args.force:
                print(f"[cached] {tag}")
                continue
            try:
                rec = run_cell(arch, shape, multi, remat_policy=args.remat,
                               regime=args.regime,
                               dist_decode=args.dist_decode)
                if "skipped" in rec:
                    n_skip += 1
                    print(f"[skip]   {tag}: {rec['skipped']}")
                else:
                    n_ok += 1
                    r = rec["roofline"]
                    print(f"[ok]     {tag}: compile={rec['compile_s']}s "
                          f"mem={rec['memory']['peak_per_device_gb']}GB "
                          f"dom={r['dominant']} "
                          f"(c={r['compute_s']:.2e} m={r['memory_s']:.2e} "
                          f"coll={r['collective_s']:.2e})")
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                n_fail += 1
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if multi else "16x16",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
                print(f"[FAIL]   {tag}: {type(e).__name__}: {str(e)[:200]}")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
    print(f"done: ok={n_ok} skip={n_skip} fail={n_fail}")


if __name__ == "__main__":
    main()
