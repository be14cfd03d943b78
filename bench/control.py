"""The control for `correct`: the cell's own run with its guarantee broken.

    python bench/control.py --workload <cell> --seconds <s> --budget <n> \
        --seeds 11,12,13

The configuration guarantees fresh answers (staleness budget 0).  The
control runs the program's own path with a staleness budget of `--budget`
pending triples, the step a later change might take to save maintenance
passes, at the cell's size and load, and prints each seed's checks as
one JSON line.  Its answers must come out as not correct.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--budget", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    if not harness.start_jax("control", harness.Cell(args.workload).chips):
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(
            args.workload, seed, args.seconds, t_start=time.perf_counter(),
            maintenance={"staleness_budget": args.budget})
        print(json.dumps({"seed": seed, "budget": args.budget,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
