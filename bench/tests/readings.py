"""Readings that set each cell's correctness limits (run on the chip).

For each seed, in one process (compiled programs stay warm across seeds),
runs one short window of the cell exactly as ``run.py`` does and records
the numbers the check compares for the program and for the control (the
float32 reference in the program's place):

    python bench/tests/readings.py --workload <cell> --seeds 101-112 \
        --seconds 5 > readings.json
"""

import argparse
import json
import os
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
from spec import Cell, root_of  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    lo, hi = (int(x) for x in args.seeds.split("-"))
    root = root_of(BENCH)
    cell = Cell(args.workload, root)
    facts, error = harness.device_facts(cell.chips, True)
    if error:
        sys.exit(error)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"][facts["kind"]]
    harness.enable_cache(os.path.join(root, ".bench_cache", "jax"))
    sys.path.insert(0, os.path.join(root, "src"))
    rows = []
    for seed in range(lo, hi + 1):
        run_args = harness.parse(["--workload", args.workload, "--seed",
                                  str(seed), "--seconds", str(args.seconds),
                                  "--control", "1"])
        with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
            r = harness.run_cell(run_args, cell, time.perf_counter(), facts,
                                 peaks, tmp)
        rows.append({"seed": seed, "correct": r["correct"],
                     "attempted": r["attempted"], "checked": r["checked"],
                     "check_s": r["check_s"],
                     "program": {k: v["value"] for k, v in r["checks"].items()},
                     "control": r["control"]})
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    print(json.dumps({"workload": args.workload, "device": facts,
                      "rows": rows}))


if __name__ == "__main__":
    main()
