"""Compile-time scaling of the shape-bucketed workload executor.

The bucketed lowering (query/buckets.py) compiles one `lax.scan` body
per distinct shape, so its compile time should stay near-flat as the
workload grows from 22 to 1000+ members drawn from a fixed template
vocabulary (a lowering that traced one closure per DAG node would grow
linearly instead).

The sweep synthesizes distinct LUBM-vocabulary queries (same shapes,
different constants), clears the persistent compile cache at every
point (cold-compile measurement), runs the bucketed executor, and
checks every answer bit-identically against the numpy reference engine.

Gate (CI runs the quick sweep): cold compile time at the largest point
must stay within `THRESHOLD`x the smallest point — super-linear compile
scaling fails the job.  Full mode covers 22 -> 1000 members for the
acceptance table in docs/query_pipeline.md.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.bench_common import (emit, quick_mode, time_us,
                                     write_bench_json)
from repro.core.queries import Atom, CQ, Const, Var
from repro.query import engine as E
from repro.query import ref_engine as R
from repro.query.buckets import clear_compile_cache, compile_cache
from repro.query.dag import build_dag
from repro.query.plan import plan_for_cq
from repro.query.workload import WorkloadExecutor
from repro.rdf.generator import generate

THRESHOLD = 3.0  # max allowed compile-time ratio, largest vs smallest N


# ----------------------------------------------------------------------
# synthetic workload: fixed template shapes, growing constant supply
# ----------------------------------------------------------------------
def synth_workload(uni, n: int) -> list[CQ]:
    """`n` distinct conjunctive queries over the LUBM vocabulary.

    Five templates (three single-scan shapes, two join shapes) are
    drawn round-robin; successive queries of one template differ only
    in their bound constants, so workload growth adds *members*, not
    *shapes* — the regime the bucketed executor targets.  The course-
    pair template supplies O(|courses|^2) distinct queries, so a small
    universe sustains 1000+ members.
    """
    d = uni.dictionary
    takes = Const(d.lookup("ub:takesCourse"))
    member = Const(d.lookup("ub:memberOf"))
    teacher = Const(d.lookup("ub:teacherOf"))
    t = np.asarray(uni.store.triples)
    courses = [int(c) for c in np.unique(t[t[:, 1] == takes.id][:, 2])]
    depts = [int(c) for c in np.unique(t[t[:, 1] == member.id][:, 2])]
    x, y = Var("x"), Var("y")

    def t_takes():
        for c in courses:
            yield (x,), (Atom(x, takes, Const(c)),)

    def t_member():
        for dep in depts:
            yield (x,), (Atom(x, member, Const(dep)),)

    def t_teacher():
        for c in courses:
            yield (y,), (Atom(y, teacher, Const(c)),)

    def t_dept_course():
        for dep in depts:
            for c in courses:
                yield (x,), (Atom(x, takes, Const(c)),
                             Atom(x, member, Const(dep)))

    def t_course_pair():
        for i, c1 in enumerate(courses):
            for c2 in courses[i + 1:]:
                yield (x,), (Atom(x, takes, Const(c1)),
                             Atom(x, takes, Const(c2)))

    streams = [t_takes(), t_member(), t_teacher(), t_dept_course(),
               t_course_pair()]
    out: list[CQ] = []
    while len(out) < n and streams:
        alive = []
        for s in streams:
            head_atoms = next(s, None)
            if head_atoms is None:
                continue
            alive.append(s)
            head, atoms = head_atoms
            out.append(CQ(head, atoms, name=f"q{len(out)}"))
            if len(out) == n:
                return out
        streams = alive
    raise ValueError(f"template supply exhausted at {len(out)} < {n} "
                     f"queries; grow the universe")


def _sorted_rows(rows) -> np.ndarray:
    a = np.asarray(rows, np.int64)
    if a.size == 0:
        return np.zeros((0,), np.int64)
    a = a.reshape(len(a), -1)
    return a[np.lexsort(a.T[::-1])].ravel()


def check_exact(uni, qs: list[CQ], roots) -> int:
    """Bit-identical comparison against the reference engine: sorted
    result arrays must be exactly equal.  Returns the mismatch count."""
    bad = 0
    for q in qs:
        got = _sorted_rows(E.to_numpy(roots[q.name]))
        want = _sorted_rows(sorted(R.evaluate_cq(q, uni.store).as_set()))
        if not np.array_equal(got, want):
            bad += 1
    return bad


# ----------------------------------------------------------------------
def main(lines: list[str]) -> None:
    quick = quick_mode()
    if quick:
        uni = generate(n_universities=1, seed=0, dept_per_univ=2,
                       prof_per_dept=4, stud_per_dept=12, course_per_dept=5)
        sweep = [8, 32, 64]
    else:
        uni = generate(n_universities=2, seed=0, dept_per_univ=4,
                       prof_per_dept=4, stud_per_dept=20, course_per_dept=8)
        sweep = [22, 64, 128, 256, 512, 1000]
    tt = E.tt_device_indexes(uni.store)

    metrics: dict = {"quick": int(quick), "threshold": THRESHOLD,
                     "members_min": sweep[0], "members_max": sweep[-1]}
    compile_s: dict[int, float] = {}
    for n in sweep:
        qs = synth_workload(uni, n)
        dag = build_dag({q.name: plan_for_cq(q) for q in qs})

        clear_compile_cache()  # measure cold compiles at every point
        wl = WorkloadExecutor(dag, uni.store.stats, {}, max_retries=24)
        t0 = time.perf_counter()
        roots = wl.run(tt, {})
        first_s = time.perf_counter() - t0
        mismatches = check_exact(uni, qs, roots)
        assert mismatches == 0, (
            f"{mismatches} results differ from ref_engine at N={n}")

        def run():
            out = wl.run(tt, {})
            next(iter(out.values())).n.block_until_ready()

        steady_us = time_us(run, warmup=1, iters=3)
        t = wl.telemetry()
        compile_s[n] = t["bucket_compile_seconds"]
        st = dag.stats()
        metrics[f"compile_s_{n}"] = t["bucket_compile_seconds"]
        metrics[f"first_run_s_{n}"] = first_s
        metrics[f"steady_us_{n}"] = steady_us
        metrics[f"buckets_{n}"] = t["buckets"]
        metrics[f"bucket_signatures_{n}"] = t["bucket_signatures"]
        metrics[f"bucket_compiles_{n}"] = t["bucket_compiles"]
        metrics[f"recompiles_{n}"] = t["recompiles"]
        metrics[f"dag_nodes_{n}"] = st["dag_nodes"]
        lines.append(emit(
            f"compile_scale.bucketed.n{n}", steady_us,
            f"compile_s={t['bucket_compile_seconds']:.2f} "
            f"buckets={t['buckets']} dag_nodes={st['dag_nodes']}"))

    ratio = compile_s[sweep[-1]] / max(compile_s[sweep[0]], 1e-9)
    metrics["compile_ratio"] = ratio
    metrics["compile_cache_entries_last"] = compile_cache().stats()["entries"]
    lines.append(emit("compile_scale.ratio", 0.0,
                      f"{ratio:.2f}x over {sweep[0]}->{sweep[-1]} members "
                      f"(threshold {THRESHOLD}x)"))
    write_bench_json("compile_scale", metrics)
    assert ratio <= THRESHOLD, (
        f"compile time grew {ratio:.2f}x from {sweep[0]} to {sweep[-1]} "
        f"members (> {THRESHOLD}x): bucketed compile scaling regressed")
