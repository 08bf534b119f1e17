"""Find the knee of an open-loop cell once, on the chip: the highest rate,
in 10% steps, at which the waiting queue does not grow over a window.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --start <req/s> --seconds <s> [--steps <k>]

One process builds the cell's engine once, warms every prompt shape, then
offers the cell's traffic, from one seed, at ``start * 1.1**k`` for
k = 0, 1, ...; between rates the engine is drained (admission closed,
running requests evicted).  After the warm-up it prints the slots, the
device memory peak so far, the decode regime and the pool's layout; then
for each rate one JSON line:
output tokens/s beside the answer tokens of the requests due in the
window over its length, TTFT p50/p95 and the mean waiting-queue length
in the first and last third of the window.
The queue grows where the last third's mean exceeds the first third's by
more than the rate times one second (one second of arrivals).  The sweep
stops after two growing rates in a row.

A short window can hide a queue that grows slowly: at a slow step the
backlog of a rate well past capacity adds less than one second of
arrivals in a window.  So the last line names the knee by capacity too
(``knee``): the served rate's plateau, the most output tokens/s any rate
read, over the mix's mean answer is the capacity in requests/s, and the
knee is the highest rate, none of whose queues up to it grew, whose
answers the capacity covers.  The cell's ``rate_per_s`` is then set by
hand to about 0.8 of the knee, and the sweep is recorded in ``PERF.md``;
the benchmark's own runs never search for a rate.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

CHIP = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))


def knee(rows: list, mean_answer: float) -> dict:
    """The capacity (req/s) that the sweep's rows show and the highest
    rate under it with no growing queue up to it; ``knee`` None where the
    first rate is already past capacity (start lower)."""
    capacity = max(r["output_tok_s"] for r in rows) / mean_answer
    best = None
    for r in sorted(rows, key=lambda r: r["rate_per_s"]):
        if r["grows"] or r["rate_per_s"] > capacity:
            break
        best = r["rate_per_s"]
    return {"capacity_req_s": capacity, "mean_answer": mean_answer,
            "knee": best}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--steps", type=int, default=16)
    args = ap.parse_args(argv)

    from harness import reading, serve_loop, spec, traffic_gen
    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        print("a closed-loop cell has no rate to sweep", file=sys.stderr)
        return 2
    serve_loop.configure_caches()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    runner = serve_loop.Runner(cell, args.seed, args.seconds, False,
                               time.perf_counter(), log=print)
    runner.build()
    growing = 0
    rows = []
    with runner.mesh_context():
        for k in range(args.steps):
            rate = args.start * 1.1 ** k
            runner.traffic = traffic_gen.Traffic(cell.traffic, args.seed,
                                                 rate=rate)
            runner._n_issued = 0
            runner.reqs, runner.order, runner.steps = {}, [], []
            if k == 0:
                runner.warm_up()
                print(json.dumps({
                    "slots": cell.params["slots"],
                    "memory_peak_bytes": serve_loop.device_peak_bytes(
                        jax.devices()[: cell.chips]),
                    "regime": serve_loop.regime_line(runner.engine),
                    "pool": serve_loop.cache_layout(runner.engine.cache)}),
                    flush=True)
            else:
                runner._t0 = time.perf_counter()
                runner._drive(runner._t0, runner._t0 + float(
                    cell.traffic["warmup_s"]))
            queue = []
            orig = runner._step

            def step():
                orig()
                queue.append((time.perf_counter(),
                              len(runner.engine.queue)))
            runner._step = step
            rec = runner.window()
            runner._step = orig
            third = args.seconds / 3
            first = [q for t, q in queue if t < rec.t_start + third]
            last = [q for t, q in queue if t >= rec.t_end - third]
            mean = lambda v: sum(v) / len(v) if v else 0.0  # noqa: E731
            grows = mean(last) - mean(first) > rate
            ttft = reading.ttft(rec)
            due = reading.due_in_window(rec)
            rows.append({
                "rate_per_s": rate, "due": len(due),
                "offered_tok_s": sum(r.max_new for r in due) / rec.window_s,
                "output_tok_s": reading.window_tokens(rec) / rec.window_s,
                "ttft_p50_ms": 1e3 * reading.percentile(ttft, 50),
                "ttft_p95_ms": 1e3 * reading.percentile(ttft, 95),
                "itl_p50_ms": 1e3 * reading.percentile(
                    reading.itl_gaps(rec), 50),
                "queue_first_third": mean(first),
                "queue_last_third": mean(last), "grows": grows})
            print(json.dumps(rows[-1]), flush=True)
            runner.engine.drain(deadline=0.0)
            growing = growing + 1 if grows else 0
            if growing >= 2:
                break
    mean_answer = float(traffic_gen.output_block(cell.traffic).mean())
    print(json.dumps(knee(rows, mean_answer)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
