"""Paper demo finale + workload-compilation A/B.

Part 1 (the demo's performance claim): per-query latency answered from
the triple table vs from the wizard's materialized views.

Part 2 (workload-level compilation): one-member programs — each
workload member lowered alone, the serving ladder's tier-1 form — vs
the fused shared-subplan program — ONE program for the entire workload
(query/dag.py + workload.py).  Both legs run the same bucketed lowering
(query/buckets.py), so the difference isolates subplan sharing and
single-pass dispatch.  Reports compile count, compile time, per-workload
latency, and the DAG's shared-node hit rate; the speedup lands in
BENCH_query_eval.json.
"""
from __future__ import annotations

import time

from benchmarks.bench_common import (emit, quick_mode, time_us,
                                     write_bench_json)
from repro.core.search import SearchConfig
from repro.core.wizard import WizardConfig, tune
from repro.query import engine as E
from repro.query.buckets import clear_compile_cache
from repro.query.dag import build_dag
from repro.query.plan import plan_for_cq
from repro.query.workload import WorkloadExecutor
from repro.rdf.generator import generate, lubm_workload


def _one_member(name: str, plan, stats) -> WorkloadExecutor:
    """`plan` lowered alone over the triple table (no views)."""
    return WorkloadExecutor(build_dag({name: plan}), stats, {})


def _run_all(programs, tt, views) -> None:
    for wl in programs:
        roots = wl.run(tt, views)
        next(iter(roots.values())).n.block_until_ready()


def main(lines: list[str]) -> None:
    quick = quick_mode()
    uni = generate(n_universities=1 if quick else 4, seed=0)
    workload = lubm_workload(uni.dictionary)
    rep = tune(uni.store, workload, uni.schema, uni.type_id,
               WizardConfig(search=SearchConfig(strategy="greedy",
                                                max_states=60 if quick
                                                else 300)))
    ex = rep.executor
    tt = E.tt_device_indexes(uni.store)

    # ------------------------------------------------------------------
    # part 1: TT vs materialized views, per query group
    # ------------------------------------------------------------------
    speedups = []
    for q in workload:
        # baseline: every reformulation member evaluated over the TT
        members = [m for m in rep.result.best.queries
                   if m.name in rep.groups[q.name]]
        base = [_one_member(m.name, plan_for_cq(m), uni.store.stats)
                for m in members]
        views = [ex.per_query_program(name) for name in rep.groups[q.name]]

        us_base = time_us(lambda: _run_all(base, tt, {}))
        us_views = time_us(lambda: _run_all(views, ex.tt, ex.device_views))
        speedups.append(us_base / max(us_views, 1e-9))
        lines.append(emit(f"query_eval.{q.name}.tt", us_base,
                          f"members={len(members)}"))
        lines.append(emit(f"query_eval.{q.name}.views", us_views,
                          f"speedup={us_base / max(us_views, 1e-9):.2f}x"))
    geo = 1.0
    for s in speedups:
        geo *= s
    geo **= 1.0 / len(speedups)
    lines.append(emit("query_eval.geomean_speedup", 0.0, f"{geo:.2f}x"))

    # ------------------------------------------------------------------
    # part 2: one-member programs vs the fused workload program
    # (both over the no-views baseline plans: identical physical work,
    #  so the delta isolates sharing + single-pass dispatch)
    # ------------------------------------------------------------------
    members = list(rep.result.best.queries)
    plans = {m.name: plan_for_cq(m) for m in members}

    # per-query leg: one program per member, cold compile cache
    clear_compile_cache()
    per_q = [_one_member(name, p, uni.store.stats)
             for name, p in plans.items()]
    t0 = time.perf_counter()
    _run_all(per_q, tt, {})  # first run = compile
    perq_compile_us = (time.perf_counter() - t0) * 1e6
    perq_compiles = sum(wl.compiles for wl in per_q)
    perq_bucket_compiles = sum(wl.telemetry()["bucket_compiles"]
                               for wl in per_q)
    perq_us = time_us(lambda: _run_all(per_q, tt, {}))

    # fused leg: one program for the whole workload, cold compile cache
    clear_compile_cache()
    dag = build_dag(plans)
    wl = WorkloadExecutor(dag, uni.store.stats, {})
    t0 = time.perf_counter()
    wl.run(tt, {})  # compile + first run (adaptive driver)
    fused_compile_us = (time.perf_counter() - t0) * 1e6
    fused_compiles = wl.compiles
    fused_us = time_us(lambda: _run_all([wl], tt, {}))
    ft = wl.telemetry()
    st = dag.stats()
    workload_speedup = perq_us / max(fused_us, 1e-9)

    lines.append(emit("query_eval.workload.per_query", perq_us,
                      f"compiles={perq_compiles} "
                      f"bucket_compiles={perq_bucket_compiles}"))
    lines.append(emit("query_eval.workload.fused", fused_us,
                      f"compiles={fused_compiles} "
                      f"bucket_compiles={ft['bucket_compiles']} "
                      f"buckets={ft['buckets']} "
                      f"shared={st['shared_nodes']} "
                      f"hit_rate={st['hit_rate']:.2f}"))
    lines.append(emit("query_eval.workload.speedup", 0.0,
                      f"{workload_speedup:.2f}x"))

    assert fused_compiles < perq_compiles, (
        "fused executor must compile strictly fewer programs")

    write_bench_json("query_eval", {
        "geomean_tt_vs_views_speedup": geo,
        "workload_members": len(members),
        "per_query_compile_us": perq_compile_us,
        "per_query_compiles": perq_compiles,
        "per_query_bucket_compiles": perq_bucket_compiles,
        "per_query_workload_us": perq_us,
        "fused_compile_us": fused_compile_us,
        "fused_compiles": fused_compiles,
        "fused_bucket_compiles": ft["bucket_compiles"],
        "fused_buckets": ft["buckets"],
        "fused_workload_us": fused_us,
        "fused_recompiles": wl.recompiles,
        "dag_nodes": st["dag_nodes"],
        "tree_nodes": st["tree_nodes"],
        "shared_nodes": st["shared_nodes"],
        "node_reuse_count": st["node_reuse_count"],
        "shared_node_hit_rate": st["hit_rate"],
        "workload_speedup": workload_speedup,
    })
