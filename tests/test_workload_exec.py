"""Workload-level compilation: shared-subplan DAG, fused jitted
executor, adaptive capacity recovery, device materialization, serving."""
import numpy as np
import pytest

from repro.core.queries import Atom, CQ, Const, Var
from repro.core.reformulation import reformulate_workload
from repro.core.search import SearchConfig
from repro.core.wizard import WizardConfig, tune
from repro.query import engine as E
from repro.query import ref_engine as R
from repro.query.buckets import clear_compile_cache
from repro.query.dag import build_dag
from repro.query.plan import TTScan, plan_for_cq
from repro.query.workload import WorkloadExecutor
from repro.rdf.generator import generate, lubm_workload
from repro.serve.query_server import QueryServer
from repro.views.materializer import (materialize_state,
                                      materialize_state_device)


@pytest.fixture(scope="module")
def uni():
    return generate(n_universities=1, seed=0, dept_per_univ=2,
                    prof_per_dept=4, stud_per_dept=12, course_per_dept=5)


@pytest.fixture(scope="module")
def members(uni):
    ms, groups = reformulate_workload(
        list(lubm_workload(uni.dictionary)), uni.schema, uni.type_id, 2048)
    return ms, groups


@pytest.fixture(scope="module")
def baseline_dag(members):
    ms, _ = members
    return build_dag({m.name: plan_for_cq(m) for m in ms})


@pytest.fixture(scope="module")
def report(uni):
    cfg = WizardConfig(search=SearchConfig(strategy="greedy", max_states=400))
    return tune(uni.store, lubm_workload(uni.dictionary), uni.schema,
                uni.type_id, cfg)


# ----------------------------------------------------------------------
# DAG canonicalization + sharing
# ----------------------------------------------------------------------
def test_dag_shares_subplans_across_rewritings(baseline_dag):
    """Distinct rewritings of the workload must share at least one node,
    visible through the DAG's node-reuse counter."""
    st = baseline_dag.stats()
    assert st["shared_nodes"] >= 1
    assert baseline_dag.node_reuse_count >= 1
    assert st["dag_nodes"] < st["tree_nodes"]


def test_dag_sharing_is_renaming_invariant(uni):
    """The same triple pattern under different variable names interns to
    one scan node; different constants stay distinct."""
    from repro.core.queries import Atom, CQ, Const, Var

    takes = Const(uni.dictionary.lookup("ub:takesCourse"))
    adv = Const(uni.dictionary.lookup("ub:advisor"))
    q1 = CQ((Var("x"),), (Atom(Var("x"), takes, Var("y")),), name="a")
    q2 = CQ((Var("s"),), (Atom(Var("s"), takes, Var("t")),), name="b")
    q3 = CQ((Var("s"),), (Atom(Var("s"), adv, Var("t")),), name="c")
    dag = build_dag({q.name: plan_for_cq(q) for q in (q1, q2, q3)})
    kinds = [n.kind for n in dag.nodes]
    assert kinds.count("scan") == 2  # q1/q2 share, q3 distinct
    assert dag.roots["a"] == dag.roots["b"]  # whole rewriting deduped


def test_fused_executor_matches_oracle(uni, members, baseline_dag):
    """One device call answers every workload member identically to
    direct evaluation (set semantics)."""
    ms, _ = members
    wl = WorkloadExecutor(baseline_dag, uni.store.stats, {})
    roots = wl.run(E.tt_device_indexes(uni.store), {})
    for m in ms:
        got = {tuple(r) for r in E.to_numpy(roots[m.name]).tolist()}
        want = R.evaluate_cq(m, uni.store).as_set()
        assert got == want, m.name
    assert wl.compiles == 1 and wl.runs == 1 and wl.recompiles == 0


# ----------------------------------------------------------------------
# adaptive capacity recovery
# ----------------------------------------------------------------------
def test_overflow_recovers_by_doubling(uni, members, baseline_dag):
    """Pathologically tiny capacities overflow; the driver doubles the
    offending nodes and recompiles until every answer is exact."""
    ms, _ = members
    wl = WorkloadExecutor(baseline_dag, uni.store.stats, {},
                          cap_planner=lambda node, rows: 32, max_retries=24)
    roots = wl.run(E.tt_device_indexes(uni.store), {})
    assert wl.recompiles >= 1
    assert wl.cap_history  # some node actually grew
    for nid, hist in wl.cap_history.items():
        assert hist == sorted(hist) and hist[-1] > hist[0]
    for m in ms:
        got = {tuple(r) for r in E.to_numpy(roots[m.name]).tolist()}
        want = R.evaluate_cq(m, uni.store).as_set()
        assert got == want, m.name


def test_overflow_retry_budget_trips(uni, baseline_dag):
    wl = WorkloadExecutor(baseline_dag, uni.store.stats, {},
                          cap_planner=lambda node, rows: 2, max_retries=1)
    with pytest.raises(RuntimeError, match="overflow persists"):
        wl.run(E.tt_device_indexes(uni.store), {})
    assert wl.recompiles == 1  # budget consumed, then raised


def test_executor_answer_recovers_from_overflow(uni, report):
    """QueryExecutor no longer raises on overflow: tiny initial caps are
    recovered adaptively and answers still match the oracle."""
    from repro.core.executor import QueryExecutor

    ex = QueryExecutor(uni.store, report.result.best, report.groups,
                       cap_planner=lambda node, rows: 8, max_retries=24)
    for q in lubm_workload(uni.dictionary):
        assert ex.answer_group(q.name) == ex.answer_group_direct(q.name)
    t = ex.telemetry()
    assert t["runs"] >= 1 and t["compiles"] == t["recompiles"] + 1


# ----------------------------------------------------------------------
# shape-bucketed execution
# ----------------------------------------------------------------------
def _course_scan_workload(uni):
    """Three same-shape course scans (one bucket) + one advisor scan
    (structurally different -> its own bucket).  Every plan root is the
    scan itself, so bucket attribution is exact."""
    d = uni.dictionary
    takes = Const(d.lookup("ub:takesCourse"))
    adv = Const(d.lookup("ub:advisor"))
    x, y = Var("x"), Var("y")
    qs = [CQ((x,), (Atom(x, takes, Const(d.lookup(c))),), name=f"takes{i}")
          for i, c in enumerate(["u0.d0.c0", "u0.d0.c1", "u0.d1.c0"])]
    qs.append(CQ((x, y), (Atom(x, adv, y),), name="adv"))
    return qs, takes


def test_overflow_promotes_only_offending_bucket(uni):
    """Force an overflow inside ONE bucket: the adaptive driver promotes
    that bucket to the next capacity class and recompiles ONLY its body
    — the other bucket never recompiles — and answers stay exact."""
    clear_compile_cache()
    qs, takes = _course_scan_workload(uni)
    dag = build_dag({q.name: plan_for_cq(q) for q in qs})

    def planner(plan, rows):
        if isinstance(plan, TTScan) and plan.atom.p == takes:
            return 2  # guaranteed too small: every course has >2 takers
        return 512

    wl = WorkloadExecutor(dag, uni.store.stats, {}, cap_planner=planner,
                          max_retries=16)
    roots = wl.run(E.tt_device_indexes(uni.store), {})
    for q in qs:
        got = {tuple(r) for r in E.to_numpy(roots[q.name]).tolist()}
        assert got == R.evaluate_cq(q, uni.store).as_set(), q.name
    assert wl.recompiles >= 1
    t = wl.telemetry()
    promoted = [b for b in wl._prog.buckets if b.promotions > 0]
    assert len(promoted) == 1  # exactly one bucket grew...
    assert promoted[0].kind == "scan" and len(promoted[0].node_ids) == 3
    # ...and every compile past the initial set recompiled THAT bucket:
    # same 3-member batch, at a promoted capacity class
    log = t["bucket_compile_log"]
    assert len(log) == t["buckets"] + promoted[0].promotions
    for entry in log[t["buckets"]:]:
        assert entry["kind"] == "scan"
        assert entry["batch"] == 3 and entry["cap"] > 2
    # untouched bucket compiled exactly once across all retries
    assert sum(1 for e in log if e["batch"] == 1) == 1
    assert t["bucket_promotions"] == promoted[0].promotions


def test_bucketed_matches_unrolled(uni):
    """The bucketed lowering answers every member of the course-scan
    workload exactly as the reference engine does."""
    qs, _ = _course_scan_workload(uni)
    dag = build_dag({q.name: plan_for_cq(q) for q in qs})
    tt = E.tt_device_indexes(uni.store)
    roots = WorkloadExecutor(dag, uni.store.stats, {}).run(tt, {})
    assert set(roots) == {q.name for q in qs}
    for q in qs:
        got = {tuple(r) for r in E.to_numpy(roots[q.name]).tolist()}
        assert got == R.evaluate_cq(q, uni.store).as_set(), q.name


def test_learned_caps_carry_to_successor(uni, members, baseline_dag):
    """Capacities grown adaptively carry into a successor executor over
    a fresh DAG instance: the successor never re-learns them."""
    ms, _ = members
    tt = E.tt_device_indexes(uni.store)
    wl1 = WorkloadExecutor(baseline_dag, uni.store.stats, {},
                           cap_planner=lambda node, rows: 32, max_retries=24)
    wl1.run(tt, {})
    assert wl1.recompiles >= 1
    carry = wl1.learned_caps()
    assert carry  # keyed by content key, not node id
    assert all(isinstance(k, tuple) for k in carry)

    dag2 = build_dag({m.name: plan_for_cq(m) for m in ms})  # fresh ids
    wl2 = WorkloadExecutor(dag2, uni.store.stats, {},
                           cap_planner=lambda node, rows: 32, max_retries=24,
                           carry_caps=carry)
    roots = wl2.run(tt, {})
    assert wl2.recompiles == 0  # healed capacities carried over
    for m in ms:
        got = {tuple(r) for r in E.to_numpy(roots[m.name]).tolist()}
        assert got == R.evaluate_cq(m, uni.store).as_set(), m.name


def test_swap_state_carries_caps_and_prewarms(uni):
    """The hot-swap path threads learned capacities into the incoming
    program and pre-warms it: after swap_state the results cache is
    already seeded and nothing re-learns old overflows."""
    from repro.core.executor import QueryExecutor
    from repro.core.state import State

    qs, takes = _course_scan_workload(uni)
    # a state executing straight off the TT (scan nodes that CAN overflow
    # — the tiny LUBM instance tunes to view-only rewritings otherwise)
    state = State(views={}, queries=tuple(qs),
                  rewritings={q.name: plan_for_cq(q) for q in qs})
    groups = {q.name: [q.name] for q in qs}

    def planner(plan, rows):
        if isinstance(plan, TTScan) and plan.atom.p == takes:
            return 2
        return 512

    ex = QueryExecutor(uni.store, state, groups, cap_planner=planner,
                       max_retries=16)
    ex.answer_workload()
    assert ex.workload.recompiles >= 1
    grown = ex.workload.learned_caps()
    assert grown  # tiny caps forced adaptive growth
    ex.swap_state(state, groups)  # warm=True default
    assert ex.workload.carry_caps == grown
    assert ex.workload.recompiles == 0
    assert ex.workload.runs >= 1  # pre-warmed on the swap
    assert ex._results is not None  # serving cache seeded
    for q in qs:
        assert ex.answer_group(q.name) == ex.answer_group_direct(q.name)


def test_bucket_telemetry_reaches_server_stats(uni, report):
    srv = QueryServer(report.executor)
    srv.answer_batch([q.name for q in lubm_workload(uni.dictionary)])
    t = report.executor.telemetry()
    assert t["buckets"] >= 1
    assert t["bucket_compiles"] + t["bucket_cache_hits"] >= t["buckets"]
    assert t["compile_cache"]["entries"] >= 1
    s = srv.stats
    assert s.buckets == t["buckets"]
    assert s.bucket_compiles == t["bucket_compiles"]
    assert s.bucket_cache_hits == t["bucket_cache_hits"]
    assert s.bucket_cache_misses == t["bucket_compiles"]
    assert s.bucket_compile_seconds == t["bucket_compile_seconds"]
    assert s.compile_cache_entries == t["compile_cache"]["entries"]


# ----------------------------------------------------------------------
# executor integration
# ----------------------------------------------------------------------
def test_executor_single_device_call_for_workload(uni, report):
    ex = report.executor
    ex.answer_workload()
    first_runs = ex.workload.runs
    # every member answer comes from the same cached fused run
    for name in ex.dag.roots:
        got = {tuple(r) for r in ex.answer(name).tolist()}
        assert got == ex.answer_direct(name), name
    assert ex.workload.runs == first_runs
    assert ex.workload.compiles >= 1


def test_legacy_per_query_path_matches(uni, report):
    """Tier 1 (each member run alone) answers every group exactly."""
    ex = report.executor
    for name in list(ex.groups)[:4]:
        assert ex.answer_group_per_query(name) == \
            ex.answer_group_direct(name), name


def test_per_query_tier_heals_overflow(uni):
    """A tier-1 member whose planned capacity overflows on its first run
    is promoted by the adaptive driver and answers exactly."""
    from repro.core.executor import QueryExecutor
    from repro.core.state import State

    qs, takes = _course_scan_workload(uni)
    state = State(views={}, queries=tuple(qs),
                  rewritings={q.name: plan_for_cq(q) for q in qs})

    def planner(plan, rows):
        if isinstance(plan, TTScan) and plan.atom.p == takes:
            return 2  # every course has more than 2 takers
        return 512

    ex = QueryExecutor(uni.store, state, cap_planner=planner,
                       max_retries=16)
    for q in qs:
        assert ex.answer_group_per_query(q.name) == \
            ex.answer_group_direct(q.name), q.name
    healed = [q.name for q in qs
              if ex.per_query_program(q.name).recompiles >= 1]
    assert len(healed) == 3  # the three takesCourse members
    assert ex.workload.runs == 0  # tier 1 never ran the fused program


# ----------------------------------------------------------------------
# device materialization
# ----------------------------------------------------------------------
def test_device_materialization_matches_oracle(uni, report):
    state = report.result.best
    ext_o, dev_o, info_o = materialize_state(state, uni.store)
    ext_d, dev_d, info_d = materialize_state_device(state, uni.store)
    assert set(ext_o) == set(ext_d)
    for vid in ext_o:
        assert ext_o[vid].cols == ext_d[vid].cols
        assert ext_o[vid].as_set() == ext_d[vid].as_set(), vid
        assert info_o[vid].rows == info_d[vid].rows
        assert int(dev_d[vid].n) == len(ext_o[vid].rows)


def test_executor_with_device_materialization(uni, report):
    from repro.core.executor import QueryExecutor

    ex = QueryExecutor(uni.store, report.result.best, report.groups,
                       device_materialize=True)
    for q in lubm_workload(uni.dictionary):
        assert ex.answer_group(q.name) == report.executor.answer_group(q.name)


# ----------------------------------------------------------------------
# serving
# ----------------------------------------------------------------------
def test_query_server_batched_requests(uni, report):
    srv = QueryServer(report.executor)
    names = [q.name for q in lubm_workload(uni.dictionary)]
    batch = names + names[:2] + ["no_such_query"]
    answers = srv.answer_batch(batch)
    for name, ans in zip(batch, answers):
        if name == "no_such_query":
            assert ans is None
        else:
            assert ans == report.executor.answer_group_direct(name), name
    assert srv.stats.requests == len(batch)
    assert srv.stats.unknown == 1
    assert srv.stats.device_runs >= 1
    # repeat batches never trigger extra device work
    runs = srv.stats.device_runs
    srv.answer_batch(names)
    assert srv.stats.device_runs == runs


def test_server_invalidate_refreshes_after_maintenance(uni, report):
    """invalidate(new_store) re-materializes views + re-uploads the TT:
    answers reflect the maintained store, not stale device snapshots."""
    from repro.core.executor import QueryExecutor
    from repro.rdf.triples import TripleStore

    srv = QueryServer(QueryExecutor(uni.store, report.result.best,
                                    report.groups))
    q = lubm_workload(uni.dictionary)[0]
    before = srv.answer(q.name)
    assert before == srv.executor.answer_group_direct(q.name)
    # crude maintenance event: drop a third of the triple table
    t = uni.store.triples
    srv.invalidate(TripleStore(t[: int(len(t) * 0.7)], uni.dictionary))
    after = srv.answer(q.name)
    assert after == srv.executor.answer_group_direct(q.name)
