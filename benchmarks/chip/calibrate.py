"""Readings that set a cell's correctness limit, on the chip, in one
process: for each seed, a whole run of the cell (``run.execute``, with a
short window of the cell's own traffic at the cell file's own rate)
whose check compares the float8 control in the served tokens' place, or
with one of ``harness/faults.py`` planted under the engine's dispatch.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seconds <s> --seeds <n1> <n2> ... [--fault <name>]

Prints one JSON line per seed: ``correct`` as the run decides it at the
cell's own limit, the numbers compared with their limits, and (for the
control) the served tokens' widest gap, the program's reading, beside
the control's.  The limit in ``cells/<cell>.json`` is set from these by
hand, above the largest program reading and below the smallest control
reading, and the readings go to ``PERF.md``.  The benchmark's own runs
never run the control or a fault.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

CHIP = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP))


def main(argv=None) -> int:
    import run as bench
    from harness import faults, peaks, serve_loop, spec
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS), default=None)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    serve_loop.configure_caches()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    devs = devs[: cell.chips]
    pk = peaks.for_kind(devs[0].device_kind)
    fault = faults.FAULTS[args.fault] if args.fault else None
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = bench.execute(cell, seed, args.seconds, False, devs, len(devs),
                            pk, control=fault is None, fault=fault)
        gc.collect()
        print(json.dumps({"seed": seed, "run": args.fault or "control",
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"],
                          "readings": out.get("readings"),
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
