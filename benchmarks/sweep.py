"""Find a cell's capacity on the chip, once, when the cell is defined:

    python benchmarks/sweep.py <cell> <seconds> <rate> [<rate> ...]

One process builds the cell once and opens one window per rate through
the same code as run.py (rate 0: `n_requests` due at t=0, the capacity
run). For each it prints the requests admitted per second, how many
due requests were never admitted by the close (a backlog that grows
means the rate is above capacity), the tails and the tokens per
second. The rate chosen goes into the cell's file; no run searches.
"""

from __future__ import annotations

import json
import sys

from run import ROOT, NoChip, measure, prepare


def main(argv) -> int:
    name, seconds, rates = argv[0], float(argv[1]), [float(r) for r in argv[2:]]
    try:
        cell = prepare(name, seed=24, bench_file=ROOT / "BENCHMARK.json",
                       require_chip=True)
    except NoChip as e:
        print(f"benchmarks/sweep.py: {e}", file=sys.stderr)
        return 2
    for rate in rates:
        over = {"rate_rps": rate}
        if rate <= 0:
            over["n_requests"] = 4 * cell["engine"].slots
        metrics, _, result = measure(cell, seconds=seconds, trace=False,
                                     params_override=over)
        admitted = [r for r in result.requests if r.admitted_at is not None]
        print(json.dumps({"sweep": {
            "cell": name, "rate_rps": rate, "seconds": seconds,
            "due": len(result.requests), "admitted": len(admitted),
            "admitted_rps": len(admitted) / result.duration_s,
            "never_admitted": len(result.requests) - len(admitted),
            "finished": len(result.finished_requests),
            **{k: v["value"] for k, v in metrics.items()},
        }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
