"""Run one benchmark cell and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout.  Needs a TPU with at least as many
chips as the cell asks for: without one it exits 2 and prints no
result.  JAX's compilation cache is kept in `.jax_cache/` at the
checkout root, so only the first run of a cell in a checkout compiles.
The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and last `checks`, each number compared with its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    if not harness.start_jax(args.workload, harness.Cell(args.workload).chips):
        return 2
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              trace=bool(args.trace), t_start=T_START)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
