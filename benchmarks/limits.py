"""Readings for the limits of `correct`, on the chip at the cell's own
size:

    python benchmarks/limits.py <cell> <seconds> <seed> [<seed> ...]

For each seed one whole run as run.py makes it (weights, engine and
traffic from that seed, the timed path at the cell's load), then the
reference over the sample, and beside it the CONTROL: the reference
itself in the precision below the configuration's (`correct.control`
in the cell's file), whose picks go through the same judge and the
same limits. The lower reading of a limit is the largest the program
gives over the seeds, the upper the smallest the control gives
(README, "correct"). Exit 1 where the program is not correct on a
seed, or the control is.
"""

from __future__ import annotations

import gc
import json
import sys

from run import ROOT, NoChip, load_cell, run_cell


def main(argv) -> int:
    name, seconds, seeds = argv[0], float(argv[1]), [int(s) for s in argv[2:]]
    lower = load_cell(name, ROOT / "BENCHMARK.json")[2]["correct"]["control"]
    rows = []
    for seed in seeds:
        try:
            line = run_cell(name, seed=seed, seconds=seconds, trace=False,
                            lower=lower)
        except NoChip as e:
            print(f"benchmarks/limits.py: {e}", file=sys.stderr)
            return 2
        row = {"seed": seed, "correct": line["correct"],
               "program": {k: v["value"] for k, v in line["compared"].items()},
               "control_correct": line["control"]["correct"],
               "control": {k: v["value"]
                           for k, v in line["control"]["compared"].items()}}
        rows.append(row)
        print(json.dumps({"limits": row}), flush=True)
        gc.collect()
    for key in ("gap_max", "gap_mean"):
        print(json.dumps({"reading": key,
                          "program_largest": max(r["program"][key] for r in rows),
                          "control_smallest": min(r["control"][key] for r in rows)}))
    bad = [r["seed"] for r in rows
           if not r["correct"] or r["control_correct"]]
    if bad:
        print(f"benchmarks/limits.py: program not correct, or control "
              f"correct, on seeds {bad}", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
