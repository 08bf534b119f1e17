"""Record the small chip trace that ``test_chipbench_trace.py`` reduces.

    python3 tests/chipbench/record_trace.py [--out DIR]   # on a TPU v5e

Serves ``qwen3-8b-4L`` (published widths, 4 layers) through the paged
engine at 2 slots with a 64-token prompt, traces three scheduler
iterations (one admission with its prefill, then two decode steps) with
the Python tracer off, and writes to ``tests/chipbench/data/`` (or ``--out``):
``decode.xplane.pb`` (the trace) and ``decode_hlo_meta.json`` (the
``op_name`` of every instruction the trace's ops name, from the compiled
programs' HLO).
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(HERE / "data"))
    out = Path(ap.parse_args().out)
    from harness import serve_loop, spec, trace_reduce
    serve_loop.configure_caches()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cell = spec.load_cell("qwen3-8b-4L.chat")
    traffic = dict(cell.traffic, prompt=dict(cell.traffic["prompt"],
                                             snap=[64]),
                   output=dict(cell.traffic["output"], max=8))
    cell = dataclasses.replace(cell, traffic=traffic,
                               params=dict(cell.params, slots=2))
    runner = serve_loop.Runner(cell, 0, 1.0, True, time.perf_counter())
    runner.build()
    eng = runner.engine
    eng.submit(runner.traffic.warmup_tokens(64, cell.config["vocab_size"]), 3)
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
    tmp = serve_loop.TRACE_DIR
    shutil.rmtree(tmp, ignore_errors=True)
    eng.submit(runner.traffic.warmup_tokens(64, cell.config["vocab_size"]), 8)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step()
    jax.profiler.stop_trace()
    path = trace_reduce.latest_xplane(str(tmp))
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out / "decode.xplane.pb")
    meta = trace_reduce.ops_metadata(
        p.as_text() for k, p in eng.programs.items() if k[0] == 0)
    red = trace_reduce.reduce(path)
    used = {(o.module, o.instr) for o in red.ops}
    with open(out / "decode_hlo_meta.json", "w") as f:
        json.dump({m: {i: v for i, v in instrs.items() if (m, i) in used}
                   for m, instrs in meta.items()}, f)
    print(f"wrote {out}: {(out / 'decode.xplane.pb').stat().st_size} bytes "
          f"of trace, {len(red.ops)} ops")
    return 0


if __name__ == "__main__":
    sys.exit(main())
