"""Find a cell's knee: one fresh run per offered rate, in shuffled order.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 3,4,6,8 --repeats 2

Runs every rate `--repeats` times, in an order drawn from the seed, each
window in a process of its own that sets the cell up as a measured run
does (so no window finds what an earlier one compiled), and prints one
JSON line per window: what was offered and served, read latency, update
visibility, the reads still unserved when the window closed
(`backlog_at_close`), how far the last read was served past the window
(`overrun_s`), the median queue wait of the window's first and second
half of reads, the passes and the compiles in the window.  The knee is
the highest rate at which, in every repeat, the served read rate keeps
up with the offered one and the backlog at the close is at most one
read batch.  Answers are not checked here; the cell's own runs do that.
The parent process never touches JAX, so each child has the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _window(workload: str, seed: int, seconds: float, rate: float) -> dict:
    import importlib

    import jax

    from bench import harness
    from bench.driver import Driver
    from bench.numbers import nearest_rank

    cell = harness.Cell(workload)
    gen = importlib.import_module(f"bench.generators.{cell.cfg['generator']}")
    counter = harness.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    sv = harness.set_up(cell, gen, counter, seed, seconds, T_START, rate=rate)
    counter.window_open = True
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float("inf"))
    rec = Driver(sv.server, sv.sched, cell.mix["batching"]).run(
        seconds, drain_name=sv.names[0],
        on_close=lambda: setattr(counter, "window_open", False))
    ctx = harness.Context(rec, sv.setup_s, counter.in_window, None, [], "",
                          {})
    lat = ctx.read_latencies_ms()
    waits = [(d - due) * 1e3 for due, d in zip(rec.due, rec.dispatch)
             if d is not None]
    half = len(waits) // 2
    passes = sum(b.passes for b in rec.batches)
    served = sum(d is not None and d <= seconds for d in rec.done)
    return {
        "rate": rate, "seed": seed, "setup_s": sv.setup_s,
        "reads": len(rec.due), "update_batches": len(rec.update_due),
        "offered_reads_per_s": len(rec.due) / seconds,
        "served_reads_per_s": served / seconds,
        "backlog_at_close": len(rec.due) - served,
        "read_p50_ms": nearest_rank(lat, 50),
        "read_p95_ms": nearest_rank(lat, 95),
        "update_visible_p50_ms": nearest_rank(ctx.update_visible_ms(), 50),
        "overrun_s": rec.end - max(rec.due, default=0.0),
        "wait_p50_first_half_ms": nearest_rank(waits[:half], 50),
        "wait_p50_second_half_ms": nearest_rank(waits[half:], 50),
        "read_batches": len(rec.batches), "passes": passes,
        "maint_pass_ms": (sum(b.maint_s for b in rec.batches)
                          / passes * 1e3) if passes else None,
        "serve_ms_per_read": sum(b.end - b.start - b.maint_s
                                 for b in rec.batches)
        / max(1, sum(len(b.reads) for b in rec.batches)) * 1e3,
        "compiles_in_window": counter.in_window}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", help="offered rates, ops/s, comma-separated")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--rate", type=float, help="one window at this rate")
    args = ap.parse_args(argv)

    if args.rate is not None:
        sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
        from bench import harness

        if not harness.start_jax("sweep",
                                 harness.Cell(args.workload).chips):
            return 2
        print(json.dumps(_window(args.workload, args.seed, args.seconds,
                                 args.rate)), flush=True)
        return 0

    import numpy as np

    runs = [float(r) for r in args.rates.split(",")] * args.repeats
    order = np.random.default_rng(args.seed).permutation(len(runs))
    for k, i in enumerate(order.tolist()):
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--seed", str(args.seed + k), "--seconds",
             str(args.seconds), "--rate", str(runs[i])],
            cwd=ROOT, stdout=subprocess.PIPE, text=True, timeout=1500)
        if p.returncode != 0:
            print(f"sweep: window at {runs[i]} ops/s exited "
                  f"{p.returncode}", file=sys.stderr)
            return p.returncode
        print(p.stdout.strip().splitlines()[-1], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
