"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload resnet18.search --seeds 1 2 3 ...

Runs one search per seed through the cell's timed path (set up as a run
sets it up), and prints, per search, one JSON line with the program's
readings of each compared number and the control's: the plain reference
computed one precision lower (the cost model in bfloat16, the GP in
float32), put in the program's place.  A limit lies above every
program reading and below the control's (PERF.md gives both).  The
benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    try:
        bench = run.Bench(args.workload)
    except run.NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        t0 = time.perf_counter()
        w = bench.window([[seed]], 0.0, trace=False)
        line = {"workload": args.workload, "search_seed": seed,
                "seconds": time.perf_counter() - t0, "compiles": w.compiles}
        for answer in ("program", "control"):
            verdict = bench.checker.judge(w.recorder, w.results, answer)
            line[answer] = {k: c["value"]
                            for k, c in verdict["checks"].items()}
        line["rows"], line["picks"] = verdict["rows"], verdict["picks"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
