"""Shape-bucket planning, the persistent compile cache, and bucketed
execution mechanics (query/buckets.py)."""
import jax
import pytest

from repro.core.queries import Atom, CQ, Const, Var
from repro.query import engine as E
from repro.query import ref_engine as R
from repro.query.buckets import (CAP_CEIL, BucketedProgram, body_builder,
                                 clear_compile_cache, compile_cache,
                                 node_waves)
from repro.query.dag import build_dag
from repro.query.plan import plan_for_cq
from repro.rdf.generator import generate


@pytest.fixture(scope="module")
def uni():
    return generate(n_universities=1, seed=0, dept_per_univ=2,
                    prof_per_dept=4, stud_per_dept=12, course_per_dept=5)


def _queries(uni):
    """Two same-shape scans (different course constants), one
    different-shape scan, one join query."""
    d = uni.dictionary
    takes = Const(d.lookup("ub:takesCourse"))
    member = Const(d.lookup("ub:memberOf"))
    x, y = Var("x"), Var("y")
    return [
        CQ((x,), (Atom(x, takes, Const(d.lookup("u0.d0.c0"))),), name="c0"),
        CQ((x,), (Atom(x, takes, Const(d.lookup("u0.d0.c1"))),), name="c1"),
        CQ((x, y), (Atom(x, member, y),), name="m"),
        CQ((x, y), (Atom(x, takes, y),
                    Atom(x, member, Const(d.lookup("u0.d0")))), name="j"),
    ]


def _dag(uni, qs):
    return build_dag({q.name: plan_for_cq(q) for q in qs})


# ----------------------------------------------------------------------
# bucket planning
# ----------------------------------------------------------------------
def test_node_waves_topology(uni):
    dag = _dag(uni, _queries(uni))
    waves = node_waves(dag)
    for node in dag.nodes:
        for c in node.child_ids:
            assert waves[c] < waves[node.id]
    assert all(waves[n.id] == 0 for n in dag.nodes if not n.child_ids)


def test_same_shape_scans_share_a_bucket(uni):
    """Scans differing only in their bound constant are one bucket (the
    constant is scanned-over data); a structurally different scan is
    not."""
    qs = _queries(uni)
    dag = _dag(uni, qs[:3])  # c0, c1, m
    prog = BucketedProgram(dag, uni.store.stats, {},
                           cap_planner=lambda node, rows: 64)
    scan_buckets = [b for b in prog.buckets if b.kind == "scan"]
    assert sorted(len(b.node_ids) for b in scan_buckets) == [1, 2]
    shared = next(b for b in scan_buckets if len(b.node_ids) == 2)
    assert {dag.roots["c0"], dag.roots["c1"]} == set(shared.node_ids)
    # per-member constants stacked once at build time
    assert shared.pvals.shape[0] == 2


def test_buckets_split_by_capacity_class(uni):
    """Same structure, different planned capacity class -> different
    buckets (a batch must be shape-uniform)."""
    qs = _queries(uni)
    dag = _dag(uni, qs[:2])
    c0_root = dag.roots["c0"]

    def planner(plan, rows):
        # tell the two course scans apart via their bound object
        return 64 if plan.atom.o.id == qs[0].atoms[0].o.id else 128

    prog = BucketedProgram(dag, uni.store.stats, {}, cap_planner=planner)
    scan_buckets = [b for b in prog.buckets if b.kind == "scan"]
    assert len(scan_buckets) == 2
    assert {b.cap for b in scan_buckets} == {64, 128}
    assert prog.node_bucket[c0_root].cap == 64


def test_content_keys_stable_across_dag_instances(uni):
    """Content keys identify logical subtrees independent of DAG-local
    node ids — the contract behind capacity carry across hot swaps."""
    qs = _queries(uni)
    dag1 = _dag(uni, [qs[0], qs[2]])
    dag2 = _dag(uni, [qs[2], qs[1], qs[0]])  # different build order
    k1, k2 = dag1.content_keys(), dag2.content_keys()
    assert k1[dag1.roots["c0"]] == k2[dag2.roots["c0"]]
    assert k1[dag1.roots["m"]] == k2[dag2.roots["m"]]
    assert k2[dag2.roots["c0"]] != k2[dag2.roots["c1"]]


# ----------------------------------------------------------------------
# persistent compile cache
# ----------------------------------------------------------------------
def test_compile_cache_persists_across_programs(uni):
    """A rebuilt program over the same shapes pays zero compiles: every
    bucket body hits the process-global cache."""
    clear_compile_cache()
    qs = _queries(uni)
    tt = E.tt_device_indexes(uni.store)
    planner = lambda node, rows: 256

    p1 = BucketedProgram(_dag(uni, qs), uni.store.stats, {},
                         cap_planner=planner)
    roots, own = p1.execute(tt, {})
    assert not own.any()
    assert p1.cache_misses == p1.n_buckets and p1.cache_hits == 0
    assert p1.compile_seconds > 0

    p2 = BucketedProgram(_dag(uni, qs), uni.store.stats, {},
                         cap_planner=planner)
    roots2, own2 = p2.execute(tt, {})
    assert not own2.any()
    assert p2.cache_misses == 0 and p2.cache_hits == p2.n_buckets
    assert compile_cache().stats()["entries"] == p1.n_buckets
    for q in qs:
        got = {tuple(r) for r in E.to_numpy(roots2[q.name]).tolist()}
        assert got == R.evaluate_cq(q, uni.store).as_set(), q.name


@pytest.mark.parametrize("role", ["workload", "delta"])
def test_bucket_bodies_are_named_by_role_and_kind(uni, role):
    """Every lowered body is `jit_<role>_<kind>`, so a device trace
    tells serving from maintenance and joins from scans."""
    prog = BucketedProgram(_dag(uni, _queries(uni)), uni.store.stats, {},
                           cap_planner=lambda node, rows: 256, role=role)
    eff = prog.static_eff_caps()
    kinds = set()
    for b in prog.buckets:
        specs = prog.abstract_args(b, len(uni.store), eff)
        fn = body_builder(b, prog.use_pallas, prog.role)
        assert fn.__name__ == f"{role}_{b.kind}"
        text = jax.jit(fn).lower(*specs).as_text()
        assert f"jit_{role}_{b.kind}" in text
        kinds.add(b.kind)
    assert {"scan", "join"} <= kinds


@pytest.mark.parametrize("left_cap, probed", [(128, True), (1 << 16, False)])
def test_join_on_a_large_scan_lowers_to_a_probe(uni, left_cap, probed):
    """A join of a small left side with a bound-predicate scan becomes a
    `probe` bucket exactly when left_cap * ceil(log2 tt_rows) < the
    scan's cap; the probed scan, consumed by nothing else, is not run,
    and the answer is the sort join's."""
    from repro.query.cost import RelInfo
    from repro.query.plan import EquiJoin, TTScan, ViewRef

    d = uni.dictionary
    x, y, z = Var("x"), Var("y"), Var("z")
    atom = Atom(x, Const(d.lookup("ub:takesCourse")), y)
    dag = build_dag({"q": EquiJoin(ViewRef(0, ("x", "z")), TTScan(atom),
                                   (("x", "x"),))})
    n_tt = len(uni.store)
    assert left_cap * (n_tt - 1).bit_length() < 4096 or not probed
    left = R.evaluate_cq(CQ((x, y), (Atom(
        x, Const(d.lookup("ub:memberOf")), y),)), uni.store)
    info = {0: R.Relation(left.rows, ("x", "z"))}
    rows = float(len(left.rows))
    prog = BucketedProgram(dag, uni.store.stats,
                           {0: RelInfo(rows, {0: rows, 1: rows})},
                           cap_planner=lambda node, rows: 4096,
                           role="delta", tt_rows=n_tt,
                           view_caps={0: left_cap})
    kinds = sorted(b.kind for b in prog.buckets)
    assert kinds == (["probe"] if probed else ["join", "scan"])
    assert prog.telemetry()["probe_joins"] == int(probed)
    for b in prog.buckets:
        specs = prog.abstract_args(b, n_tt, prog.static_eff_caps({0: left_cap}))
        text = jax.jit(body_builder(b, False, "delta")).lower(*specs).as_text()
        assert f"jit_delta_{b.kind}" in text
    roots, own = prog.execute(E.tt_device_indexes(uni.store),
                              {0: E.make_prel(left.rows, left_cap)})
    assert not own.any()
    got = {tuple(r) for r in E.to_numpy(roots["q"]).tolist()}
    want = R.execute(dag.nodes[dag.roots["q"]].plan, uni.store, info)
    assert got == want.as_set() and got


def test_roles_get_distinct_cache_keys(uni):
    """The same bucket under two roles is two cache entries: a body
    compiled under one name is never served under the other."""
    dag = _dag(uni, _queries(uni))
    planner = lambda node, rows: 256
    work = BucketedProgram(dag, uni.store.stats, {}, cap_planner=planner)
    delta = BucketedProgram(dag, uni.store.stats, {}, cap_planner=planner,
                            role="delta")
    assert work.role == "workload"
    eff = work.static_eff_caps()
    for bw, bd in zip(work.buckets, delta.buckets):
        specs = work.abstract_args(bw, len(uni.store), eff)
        assert work.cache_key(bw, specs) != delta.cache_key(bd, specs)
    clear_compile_cache()
    tt = E.tt_device_indexes(uni.store)
    work.execute(tt, {})
    delta.execute(tt, {})
    assert delta.cache_hits == 0 and delta.cache_misses == delta.n_buckets
    assert compile_cache().stats()["entries"] == 2 * work.n_buckets


# ----------------------------------------------------------------------
# promotion + padding
# ----------------------------------------------------------------------
def test_promotion_moves_whole_bucket_and_pads_consumers(uni):
    """Promoting via ONE member moves every member of the bucket to the
    next capacity class; consumers pad operands up to the new class and
    results stay oracle-exact."""
    clear_compile_cache()
    qs = _queries(uni)
    dag = _dag(uni, qs)
    tt = E.tt_device_indexes(uni.store)
    prog = BucketedProgram(dag, uni.store.stats, {},
                           cap_planner=lambda node, rows: 128)
    _, own1 = prog.execute(tt, {})
    assert not own1.any()

    scan_bucket = next(b for b in prog.buckets
                       if b.kind == "scan" and len(b.node_ids) >= 2)
    grown = prog.promote([scan_bucket.node_ids[0]])
    assert {nid for nid, _, _ in grown} == set(scan_bucket.node_ids)
    assert all(old == 128 and new == 256 for _, old, new in grown)
    assert scan_bucket.cap == 256 and scan_bucket.promotions == 1

    roots2, own2 = prog.execute(tt, {})
    assert not own2.any()
    for q in qs:
        got = {tuple(r) for r in E.to_numpy(roots2[q.name]).tolist()}
        assert got == R.evaluate_cq(q, uni.store).as_set(), q.name


def test_promotion_stops_at_ceiling(uni):
    qs = _queries(uni)
    dag = _dag(uni, qs[:1])
    prog = BucketedProgram(dag, uni.store.stats, {},
                           cap_planner=lambda node, rows: CAP_CEIL)
    assert prog.promote([dag.roots["c0"]]) == []


def test_promotion_skips_capacityless_buckets(uni):
    """Filter/project buckets have no own buffer (cap 0) — promoting
    through them is a no-op."""
    qs = _queries(uni)
    dag = _dag(uni, qs)
    prog = BucketedProgram(dag, uni.store.stats, {},
                           cap_planner=lambda node, rows: 64)
    capless = [nid for nid, b in prog.node_bucket.items() if b.cap == 0]
    if capless:  # plan shapes may or may not include filter/project
        assert prog.promote(capless) == []
