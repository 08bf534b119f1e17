"""The chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Serves the cell's traffic through the program's paged serving engine on
the chips the cell asks for, measures for ``--seconds``, then checks
what the window served against the plain float32 reference.  With
``--trace 0`` the metrics are the cell's end-to-end ones; with
``--trace 1`` its per-layer ones, read from a profiler trace of the
window's last seconds.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and ``checks`` last); the last
lines of standard error are the numbers compared, each with its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  The cell, its configuration, traffic and
metrics are found by name from ``BENCHMARK.json`` (``harness/spec.py``).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def read_metrics(entries, rec):
    """{name: {"value", "unit"}} for each metric whose reader finds
    something; notes (which roofline bound applies) go to the log."""
    from harness import spec
    out = {}
    for m in entries:
        v = spec.metric_reader(m["name"])(rec)
        if isinstance(v, tuple):
            v, note = v
            log(f"metric {m['name']}: {note}")
        if v is None:
            log(f"metric {m['name']}: nothing to read in this run")
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def breakdown(rec) -> dict:
    from collections import defaultdict
    from harness import trace_reduce
    red = rec.trace
    n_dev = max(1, len(red.devices))
    by = defaultdict(float)
    paths = {}
    for o in red.ops:
        kernel = next((k for k in ("fused_attention_paged",
                                   "fused_mlp_chain") if k in o.path), None)
        where = kernel or o.module.replace("jit_", "") or "none"
        key = f"{where}:{trace_reduce.instr_kind(o.instr)}"
        by[key] += o.self_s / n_dev
        paths.setdefault(key, o.path)
    ops = sorted(by.items(), key=lambda kv: -kv[1])[:10]
    for k, v in ops:
        log(f"device op {k} {v!r}s, jit path {paths[k][:200]!r}")
    gaps = trace_reduce.idle_gaps(red, red.devices[0])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[name, s] for s, name in gaps]}


def execute(cell, seed: int, seconds: float, trace: bool, devs,
            n_devices: int, pk, control: bool = False,
            fault=None) -> dict:
    """Set up, warm up, measure, check; the result object.  ``devs`` are
    the chips the cell uses, ``pk`` their peaks.

    The benchmark's runs leave the last two alone.  ``control`` compares,
    in the served tokens' place, the tokens that the float8 reference
    ranks first at the same positions (the control, which must come out
    not correct); ``fault`` (``harness/faults.py``) is planted under the
    engine's dispatch.  The served tokens' own gap then goes to the log
    and under ``readings``."""
    from harness import check, faults, reading, serve_loop

    runner = serve_loop.Runner(cell, seed, seconds, trace, T_PROCESS,
                               log=log)
    runner.build()
    log(serve_loop.regime_line(runner.engine))
    if fault is not None:
        faults.plant(runner.engine, fault)
    with runner.mesh_context():
        t = time.perf_counter()
        runner.warm_up()
        warm_s = time.perf_counter() - t
        log(f"KV pool after warm-up: "
            f"{serve_loop.cache_layout(runner.engine.cache)}")
        rec = runner.window()
    rec.peaks = pk
    memory_peak = serve_loop.device_peak_bytes(devs)
    log(f"set-up {rec.setup_s:.3f}s: weights {runner.weights_s:.3f}s, "
        f"engine {runner.engine_s:.3f}s, warm-up {warm_s:.3f}s")
    log(f"compiles inside the window: {rec.compiles_in_window}; "
        f"preemptions: {rec.counters_end['preemptions']}; "
        f"tier demotions: {rec.counters_end['tier_demotions']}")
    ctx = [c for s in rec.steps if rec.in_window(s.t0) for c in s.ctx]
    if ctx:
        q = reading.percentile
        log(f"contexts decoded in the window: {len(ctx)} slot-steps, mean "
            f"{sum(ctx) / len(ctx):.1f}, p5 {q(ctx, 5):.0f}, p50 "
            f"{q(ctx, 50):.0f}, p95 {q(ctx, 95):.0f}, max {max(ctx)}")
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = read_metrics(entries, rec)

    due = reading.due_in_window(rec)
    failed = sum(1 for r in due if r.outcome not in (None, "complete")
                 or (r.tokens is not None and len(r.tokens) != r.max_new))
    sample = serve_loop.sample_for_check(
        rec, int(cell.params["check_requests"]), seed)
    result_device = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": n_devices,
                     "memory_peak_bytes": memory_peak}
    extra = {}
    if trace:
        from harness import trace_reduce
        red = rec.trace
        result_device["busy_s"] = sum(
            trace_reduce.busy_s(red, d) for d in red.devices) / len(
            red.devices)
        result_device["window_s"] = red.window_s
        extra["breakdown"] = breakdown(rec)

    seqs = check.sequences(sample, runner.traffic,
                           cell.config["vocab_size"])
    rec.trace = None
    runner.release()
    del rec
    t = time.perf_counter()
    w = serve_loop.reference_weights(cell, seed, devs)
    gap, ctl, n_tok = check.gaps(w, cell, seqs, control)
    del w
    log(f"reference over {len(seqs)} requests, {n_tok} served tokens, "
        f"{time.perf_counter() - t:.3f}s on {len(devs)} chips; the "
        f"process's peak so far {serve_loop.device_peak_bytes(devs)} B")
    if control:
        log(f"served tokens' max_logit_gap {gap!r}; the control's is "
            f"compared")
        extra["readings"] = {"served_max_logit_gap": gap,
                             "control_max_logit_gap": ctl}
        gap = ctl
    limit = float(cell.params["max_logit_gap"])
    checks = {"max_logit_gap": {"value": gap, "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    correct = bool(seqs) and len(due) > 0 and failed == 0 and gap <= limit
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    out = {"correct": correct, "attempted": len(due), "failed": failed,
           "metrics": metrics, "device": result_device, **extra,
           "checks": checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import peaks, serve_loop, spec
    cell = spec.load_cell(args.workload)
    serve_loop.configure_caches()
    # the program under test: a checkout without it stops here
    import repro  # noqa: F401
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX's first device is {devices[0].platform}; this "
            f"benchmark measures the chip and has no CPU fallback")
        return 2
    if len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips, {len(devices)} found")
        return 2
    devs = devices[: cell.chips]
    out = execute(cell, args.seed, args.seconds, bool(args.trace), devs,
                  len(devices), peaks.for_kind(devs[0].device_kind))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
