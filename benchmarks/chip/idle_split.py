"""Split the device's idle time of a traced run by the serving engine's
own spans (``harness/engine_spans.py``).

    python3 benchmarks/chip/idle_split.py [--trace-dir DIR]

Reads the newest trace under ``DIR`` (by default where ``run.py --trace
1`` leaves its trace) and prints one JSON line: idle milliseconds per
decode step for each engine phase and ``bench.*`` span, idle per
admitted request, the share of step idle that no phase covers, what the
split leaves out of the traced idle (``unsplit_s``, zero up to
rounding), each admission's prompt and idle, the mean active slots and
contexts of a decode step (``engine_spans.split``), and the ten longest
idle gaps named by the span innermost when each began.  A trace with no
engine spans prints ``"split": null``.  Reads the file alone; needs no
chip.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP))


def main(argv=None) -> int:
    from harness import engine_spans, serve_loop, trace_reduce
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace-dir", default=str(serve_loop.TRACE_DIR))
    args = ap.parse_args(argv)
    path = trace_reduce.latest_xplane(args.trace_dir)
    red = engine_spans.attach(trace_reduce.reduce(path), path)
    gaps = (trace_reduce.idle_gaps(red, red.devices[0])[:10]
            if red.devices else [])
    print(json.dumps({"trace": path, "split": engine_spans.split(red),
                      "idle_gaps": [[name, s] for s, name in gaps]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
