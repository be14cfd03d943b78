"""JAX padded engine vs numpy oracle: identical answers on random data."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.queries import CQ, Atom, Const, Var
from repro.query import engine as E
from repro.query import ref_engine as R
from repro.query.buckets import BucketedProgram
from repro.query.dag import build_dag
from repro.query.plan import EquiJoin, Filter, Project, TTScan, ViewRef, plan_for_cq
from repro.query.workload import WorkloadExecutor
from repro.rdf.generator import generate, lubm_workload
from repro.rdf.triples import TripleStore


@pytest.fixture(scope="module")
def uni():
    return generate(n_universities=1, seed=0)


def _measured_info(rel):
    from repro.query.cost import RelInfo
    rows = float(len(rel.rows))
    distinct = {
        c: float(len(np.unique(rel.rows[:, i]))) if len(rel.rows) else 1.0
        for i, c in enumerate(rel.cols)
    }
    return RelInfo(max(rows, 1e-3), distinct)


def _run_plan(plan, store, views_np=None, view_cards=None, use_pallas=False):
    """`plan` as a one-member bucketed program over `store` and the
    measured extents of `views_np`."""
    views_np = views_np or {}
    view_cards = view_cards or {vid: _measured_info(rel) for vid, rel in views_np.items()}
    wl = WorkloadExecutor(build_dag({"q": plan}), store.stats, view_cards,
                          use_pallas=use_pallas)
    tt = E.tt_device_indexes(store)
    views = {
        vid: E.make_prel(rel.rows, cap=max(128, 1 << int(np.ceil(np.log2(max(len(rel.rows), 1) + 1)))))
        for vid, rel in views_np.items()
    }
    out = wl.run(tt, views)["q"]
    assert not bool(out.overflow), "capacity overflow in test plan"
    return E.to_numpy(out), plan.columns()


def _oracle(plan, store, views_np=None):
    rel = R.execute(plan, store, views_np or {})
    return rel


def assert_same(plan, store, views_np=None, view_cards=None, use_pallas=False):
    got_rows, got_cols = _run_plan(plan, store, views_np, view_cards, use_pallas)
    want = _oracle(plan, store, views_np)
    assert tuple(got_cols) == tuple(want.cols)
    got_set = {tuple(r) for r in got_rows.tolist()}
    want_set = want.as_set()
    assert got_set == want_set, (
        f"mismatch: extra={list(got_set - want_set)[:5]}, missing={list(want_set - got_set)[:5]}"
    )


def test_scan_patterns(uni):
    d = uni.dictionary
    t = Const(uni.type_id)
    student = Const(d.lookup("ub:GraduateStudent"))
    takes = Const(d.lookup("ub:takesCourse"))
    x, y = Var("x"), Var("y")
    for atom in [
        Atom(x, t, student),
        Atom(x, takes, y),
        Atom(x, Var("p"), y),
    ]:
        assert_same(TTScan(atom), uni.store)


def test_self_join_atom():
    # pattern (?x ?p ?x): rows with s == o
    t = np.array([[1, 2, 1], [1, 2, 3], [4, 5, 4]], np.int32)
    ts = TripleStore(t)
    plan = TTScan(Atom(Var("x"), Var("p"), Var("x")))
    assert_same(plan, ts)


def test_filter_and_project(uni):
    d = uni.dictionary
    takes = Const(d.lookup("ub:takesCourse"))
    x, y = Var("x"), Var("y")
    scan = TTScan(Atom(x, takes, y))
    some_course = int(uni.store.scan(None, takes.id, None)[0, 2])
    assert_same(Filter(scan, "y", some_course), uni.store)
    assert_same(Project(Filter(scan, "y", some_course), ("x",)), uni.store)


def test_join_two_atoms(uni):
    d = uni.dictionary
    t = Const(uni.type_id)
    grad = Const(d.lookup("ub:GraduateStudent"))
    takes = Const(d.lookup("ub:takesCourse"))
    x, y = Var("x"), Var("y")
    plan = EquiJoin(
        TTScan(Atom(x, t, grad)), TTScan(Atom(x, takes, y)), (("x", "x"),)
    )
    assert_same(plan, uni.store)


def test_multi_column_join(uni):
    # join on two shared vars: (x advisor y)(x memberOf z) vs (x advisor y)(y worksFor z)
    d = uni.dictionary
    adv = Const(d.lookup("ub:advisor"))
    works = Const(d.lookup("ub:worksFor"))
    member = Const(d.lookup("ub:memberOf"))
    x, y, z = Var("x"), Var("y"), Var("z")
    left = EquiJoin(TTScan(Atom(x, adv, y)), TTScan(Atom(y, works, z)), (("y", "y"),))
    right = EquiJoin(TTScan(Atom(x, member, z)), TTScan(Atom(x, adv, y)), (("x", "x"),))
    plan = EquiJoin(left, right, (("x", "x"), ("y", "y"), ("z", "z")))
    assert_same(plan, uni.store)


def test_full_workload_plans(uni):
    for q in lubm_workload(uni.dictionary):
        assert_same(plan_for_cq(q), uni.store)


def test_view_ref_and_rewriting_shape(uni):
    d = uni.dictionary
    takes = Const(d.lookup("ub:takesCourse"))
    x, y = Var("x"), Var("y")
    cq = CQ((x, y), (Atom(x, takes, y),), name="v0")
    ext = R.evaluate_cq(cq, uni.store)
    views_np = {0: ext}
    plan = Project(ViewRef(0, ("x", "y")), ("x",))
    assert_same(plan, uni.store, views_np)


def test_overflow_flag():
    t = np.stack([np.zeros(600, np.int32), np.ones(600, np.int32),
                  np.arange(600, dtype=np.int32)], axis=1)
    ts = TripleStore(t)
    plan = TTScan(Atom(Var("x"), Const(1), Var("y")))
    prog = BucketedProgram(build_dag({"q": plan}), ts.stats, {},
                           cap_planner=lambda n, r: 128)
    roots, own = prog.execute(E.tt_device_indexes(ts), {})
    out = roots["q"]
    assert bool(out.overflow) and own.any()
    assert int(out.n) == 128


def test_empty_results(uni):
    d = uni.dictionary
    t = Const(uni.type_id)
    plan = TTScan(Atom(Var("x"), t, Const(d.encode("ub:NoSuchClass"))))
    got, _ = _run_plan(plan, uni.store)
    assert len(got) == 0


# ----------------------------------------------------------------------
# index-probe join: probe == sort join == reference
# ----------------------------------------------------------------------
def _probe_store(rng, n=3000, n_ids=40):
    # few ids: every join key repeats on both sides
    tt = np.stack([rng.integers(0, n_ids, n), rng.choice([1, 2, 3], n),
                   rng.integers(0, n_ids, n)], axis=1).astype(np.int32)
    return TripleStore(tt)


def _left_prel(rows, cap, tail):
    """A left relation whose rows past `n` hold `tail` (INVALID scrub,
    or stale ids that only the valid count may exclude)."""
    buf = np.full((cap, rows.shape[1]), tail, np.int32)
    buf[: len(rows)] = rows
    return E.PRel(jnp.asarray(buf), jnp.int32(len(rows)), jnp.asarray(False))


def _probe_and_join(store, left_rows, lcols, atom, pairs, out_cap,
                    tt=None, tail=-1):
    """(probe rows, probe overflow), (sort-join rows, overflow), and the
    reference relation, for ViewRef(left) ⋈ TTScan(atom) on `pairs`."""
    tt = tt if tt is not None else E.tt_device_indexes(store)
    idx, prefix, residual, takes, self_eq, _ = E.atom_scan_spec(atom)
    rcols = TTScan(atom).columns()
    lead, rest = pairs[0], pairs[1:]
    lcol, rcol = lcols.index(lead[0]), rcols.index(lead[1])
    res = tuple((lcols.index(l), rcols.index(r)) for l, r in rest)
    drop = {r for _, r in pairs}
    keep = tuple(i for i, c in enumerate(rcols) if c not in drop)
    left = _left_prel(left_rows, 256, tail)
    scan = E.scan_pattern(tt[idx], prefix, residual, takes, self_eq, 4096)
    assert not bool(scan.overflow)
    joined = E.join(left, scan, lcol, rcol, res, keep, out_cap)
    pidx, pprefix = E.probe_scan_spec(atom, takes[rcol])
    probed = E.probe(left, tt[pidx], pprefix, takes[rcol], lcol,
                     tuple((l, takes[r]) for l, r in res),
                     tuple(takes[i] for i in keep), self_eq, out_cap)
    want = R.execute(EquiJoin(ViewRef(0, lcols), TTScan(atom), pairs), store,
                     {0: R.Relation(left_rows, lcols)})
    return probed, joined, want


def _rows_set(prel):
    return {tuple(r) for r in E.to_numpy(prel).tolist()}


P, Q = Const(1), Const(2)
X, Y, Z = Var("x"), Var("y"), Var("z")
PROBE_CASES = {
    # name: (atom, pairs, left columns, left key column values, tail)
    "duplicate_keys": (Atom(X, P, Y), (("x", "x"),), ("x", "z"), "dup", -1),
    "invalid_left_rows": (Atom(X, P, Y), (("x", "x"),), ("x", "z"), "dup", 7),
    "join_on_object": (Atom(Y, Q, X), (("x", "x"),), ("x", "z"), "dup", -1),
    "empty_range": (Atom(X, Const(9), Y), (("x", "x"),), ("x", "z"), "dup",
                    -1),
    "residual_pair": (Atom(X, P, Y), (("x", "x"), ("z", "y")), ("x", "z"),
                      "pairs", -1),
    "two_column_prefix": (Atom(X, P, Const(5)), (("x", "x"),), ("x", "z"),
                          "dup", -1),
    "two_column_prefix_subject": (Atom(Const(5), Q, X), (("x", "x"),),
                                  ("x", "z"), "dup", -1),
    "repeated_variable": (Atom(X, P, X), (("x", "x"),), ("x", "z"), "dup",
                          -1),
}


@pytest.mark.parametrize("case", [*PROBE_CASES, "sentinel_padding"])
def test_probe_join_matches_sort_join_and_reference(case):
    rng = np.random.default_rng(sorted(PROBE_CASES).index(case)
                                if case in PROBE_CASES else 99)
    store = _probe_store(rng)
    tt = None
    if case == "sentinel_padding":
        # a TT padded with SENTINEL_HI rows to a larger class, as the
        # maintainer uploads it; keys at the top of the id range
        atom, pairs, lcols, keys, tail = (Atom(Y, P, X), (("x", "x"),),
                                          ("x", "z"), "dup", -1)
        tt = E.tt_device_indexes_padded(store, 8192)
    else:
        atom, pairs, lcols, keys, tail = PROBE_CASES[case]
    n_left = 120
    left = rng.integers(0, 45, (n_left, 2)).astype(np.int32)  # some miss
    if keys == "dup":
        left[:, 0] = rng.integers(0, 45, n_left // 4).repeat(4)
    else:  # a residual pair: half the rows share both columns with a triple
        hit = store.triples[store.triples[:, 1] == 1][: n_left // 2]
        left[: len(hit)] = hit[:, [0, 2]]
    probed, joined, want = _probe_and_join(store, left, lcols, atom, pairs,
                                           1 << 14, tt=tt, tail=tail)
    assert not bool(probed.overflow) and not bool(joined.overflow)
    assert _rows_set(probed) == _rows_set(joined) == want.as_set()
    # no duplicates: the probe emits each (left row, triple) match once
    assert int(probed.n) == int(joined.n)
    if case != "empty_range":
        assert int(probed.n) > 0
    else:
        assert int(probed.n) == 0


def test_probe_join_overflow_sets_flag_and_promotion_heals():
    rng = np.random.default_rng(3)
    store = _probe_store(rng)
    left = rng.integers(0, 40, (200, 2)).astype(np.int32)
    atom = Atom(X, P, Y)
    probed, joined, want = _probe_and_join(store, left, ("x", "z"), atom,
                                           (("x", "x"),), 128)
    total = len(want.rows)
    assert total > 128
    assert bool(probed.overflow) and bool(joined.overflow)
    assert int(probed.n) <= 128
    # the bucketed program: a join planned too small for its matches
    # lowers to a probe, overflows, and the adaptive driver promotes
    # the probe bucket until the answer is whole
    plan = EquiJoin(ViewRef(0, ("x", "z")), TTScan(atom), (("x", "x"),))
    ext = R.Relation(left, ("x", "z"))
    wl = WorkloadExecutor(build_dag({"q": plan}), store.stats,
                          {0: _measured_info(ext)},
                          cap_planner=lambda node, rows:
                          128 if isinstance(node, EquiJoin) else 4096)
    out = wl.run(E.tt_device_indexes(store),
                 {0: E.make_prel(left, 256)})["q"]
    t = wl.telemetry()
    assert t["probe_joins"] == 1 and t["recompiles"] >= 1
    assert not bool(out.overflow)
    assert _rows_set(out) == want.as_set()
