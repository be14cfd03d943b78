"""Streaming incremental view maintenance (repro.maintenance).

Correctness bar: after ANY insert/delete stream, the incrementally
maintained extents and answers must equal a full re-materialization
over the final store — property-tested on the host oracle and on the
device maintainer, with a deterministic twin for the device path.
Serving bar: answers are never more than the staleness budget stale,
and injected drift triggers an automatic retune.
"""
import numpy as np
import pytest

from repro.core.queries import CQ, Atom, Const, Var
from repro.kernels import ops as kops
from repro.maintenance import (Delta, MaintenanceConfig, UpdateStream,
                               ViewMaintainer, build_delta_plans)
from repro.query import engine as E
from repro.query import ref_engine as R
from repro.rdf.triples import TripleStore, triple_keys, triples_in
from repro.views.maintenance import apply_delta, effective_delta

PREDS = [1, 2, 3, 4, 5]


def _random_store(rng, n=600, n_ids=60):
    tt = np.stack([rng.integers(0, n_ids, n), rng.choice(PREDS, n),
                   rng.integers(0, n_ids, n)], axis=1).astype(np.int32)
    return TripleStore(tt)


def _random_batch(rng, n, n_ids=60):
    return np.stack([rng.integers(0, n_ids, n), rng.choice(PREDS, n),
                     rng.integers(0, n_ids, n)], axis=1).astype(np.int32)


def _chain_cq(name, p1, p2):
    x, y, z = Var("x"), Var("y"), Var("z")
    return CQ(name=name, head=(x, y, z),
              atoms=(Atom(x, Const(p1), y), Atom(y, Const(p2), z)))


def _extent_oracle(cq, store):
    rows = R.evaluate_cq(cq, store).rows.reshape(-1, len(cq.head))
    return np.unique(np.asarray(rows, np.int32), axis=0)


def _session(store, workload):
    from repro.api import TuningSession

    s = TuningSession(store, workload=workload)
    s.retune()
    s.apply()
    return s


# ----------------------------------------------------------------------
# primitives
# ----------------------------------------------------------------------
def test_triple_keys_wide_ids_fallback():
    # ids beyond the 21-bit packing range (and negative) must still key
    # correctly through the structured-dtype fallback
    big = np.array([[1 << 22, 5, -3], [7, 8, 9]], np.int32)
    ref = np.array([[7, 8, 9], [1 << 22, 5, -3]], np.int32)
    assert triples_in(big, ref).all()
    assert not triples_in(np.array([[1 << 22, 5, 3]], np.int32), ref).any()
    assert len(np.unique(triple_keys(big))) == 2


def test_update_stream_coalesce_and_counts():
    s = UpdateStream()
    s.push(Delta.of(np.array([[1, 2, 3]], np.int32), None))
    s.push(Delta.of(np.array([[4, 5, 6]], np.int32),
                    np.array([[1, 2, 3]], np.int32)))
    s.push(Delta.of(None, None))  # empty: ignored
    assert s.pending_batches == 2 and s.pending_triples == 3
    merged = s.coalesce()
    assert s.pending_batches == 0 and s.pending_triples == 0
    # sequential semantics: the later delete of [1,2,3] overrides the
    # earlier insert in the net batch
    assert triples_in(np.array([[1, 2, 3]], np.int32), merged.deletes).all()
    assert merged.inserts.tolist() == [[4, 5, 6]]


def test_effective_delta_tie_goes_to_insert():
    store = TripleStore(np.array([[1, 1, 1], [2, 2, 2]], np.int32))
    ins = np.array([[1, 1, 1], [3, 3, 3]], np.int32)   # [1,1,1] is a dup
    dels = np.array([[1, 1, 1], [9, 9, 9]], np.int32)  # [9,9,9] absent
    eff_ins, eff_del = effective_delta(store, ins, dels)
    assert eff_ins.tolist() == [[3, 3, 3]]
    assert len(eff_del) == 0  # present, but re-inserted in the same batch


def test_scatter_append_kernel_matches_numpy():
    rng = np.random.default_rng(7)
    for cap, n, dcap, k, w in [(128, 0, 64, 0, 3), (128, 100, 64, 28, 3),
                               (256, 5, 128, 128, 2), (128, 127, 128, 1, 4)]:
        buf = np.full((cap, w), -1, np.int32)
        rows = rng.integers(0, 99, (n, w)).astype(np.int32)
        buf[:n] = rows
        batch = rng.integers(0, 99, (dcap, w)).astype(np.int32)
        out = np.asarray(kops.scatter_append(buf, n, batch, k))
        want = buf.copy()
        want[n:n + k] = batch[:k]
        np.testing.assert_array_equal(out, want)


def test_scatter_append_rejects_overflow():
    buf = np.zeros((128, 3), np.int32)
    with pytest.raises(ValueError):
        kops.scatter_append(buf, 120, np.zeros((16, 3), np.int32), 16)


# ----------------------------------------------------------------------
# host oracle: property test against full re-evaluation
# ----------------------------------------------------------------------
def test_oracle_apply_delta_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(seed=st.integers(0, 10**6), steps=st.integers(1, 4))
    def run(seed, steps):
        rng = np.random.default_rng(seed)
        cq = _chain_cq("v", int(rng.choice(PREDS)), int(rng.choice(PREDS)))
        store = _random_store(rng, n=250, n_ids=25)
        extent = _extent_oracle(cq, store)
        for _ in range(steps):
            ins = _random_batch(rng, int(rng.integers(0, 40)), n_ids=25)
            n_del = int(rng.integers(0, 30))
            dels = store.triples[rng.choice(
                len(store.triples), min(n_del, len(store.triples)),
                replace=False)]
            extent, store = apply_delta(cq, extent, store, ins, dels)
        np.testing.assert_array_equal(extent, _extent_oracle(cq, store))

    run()


# ----------------------------------------------------------------------
# device maintainer: deterministic twin + property test
# ----------------------------------------------------------------------
def _stream_and_check(seed, steps=5, batch=48, engine="auto"):
    rng = np.random.default_rng(seed)
    store = _random_store(rng)
    sess = _session(store, [_chain_cq("q1", 1, 2), _chain_cq("q2", 2, 3)])
    m = ViewMaintainer(sess.executor,
                       MaintenanceConfig(delta_cap=64, insert_engine=engine))
    for _ in range(steps):
        ins = _random_batch(rng, batch)
        n_del = int(rng.integers(0, batch))
        cur = sess.executor.store.triples
        dels = cur[rng.choice(len(cur), min(n_del, len(cur)), replace=False)]
        m.apply(Delta.of(ins, dels))
    ex = sess.executor
    for vid, view in ex.state.views.items():
        m.check_alignment(vid)  # host mirror == device valid prefix
        got = np.unique(ex.extents[vid].rows, axis=0)
        np.testing.assert_array_equal(got, _extent_oracle(view.cq, ex.store))
    for q in sess.workload:  # fused answers == oracle over final store
        assert sess.answer(q.name) == ex.answer_group_direct(q.name)
    return m


def test_maintainer_deterministic_twin():
    m = _stream_and_check(seed=1234)
    t = m.telemetry()
    # steady state must not recompile the delta program per batch
    assert t["delta_recompiles"] == 0
    assert t["measured_views"] >= 1  # costs were observed


def test_maintainer_device_engine_matches_host():
    # the fused-program insert engine (the accelerator path) must agree
    # with the vectorized host engine and stay recompile-free
    m = _stream_and_check(seed=1234, steps=3, batch=32, engine="device")
    t = m.telemetry()
    assert t["insert_engine"] == "device"
    assert t["delta_compiles"] == 1 and t["delta_recompiles"] == 0


def test_maintainer_property_random_streams():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    # few examples: each replays a full device stream (the compile cache
    # makes later examples cheap — same capacity classes)
    @settings(max_examples=4, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(seed=st.integers(0, 10**6))
    def run(seed):
        _stream_and_check(seed, steps=3, batch=32)

    run()


def test_maintainer_delete_only_and_insert_only_batches():
    rng = np.random.default_rng(9)
    store = _random_store(rng)
    sess = _session(store, [_chain_cq("q1", 1, 2)])
    m = ViewMaintainer(sess.executor, MaintenanceConfig())
    cur = sess.executor.store.triples
    r1 = m.apply(Delta.of(None, cur[:64]))
    assert r1.eff_deletes > 0 and r1.eff_inserts == 0
    r2 = m.apply(Delta.of(_random_batch(rng, 64), None))
    assert r2.eff_inserts > 0 and r2.eff_deletes == 0
    ex = sess.executor
    for vid, view in ex.state.views.items():
        got = np.unique(ex.extents[vid].rows, axis=0)
        np.testing.assert_array_equal(got, _extent_oracle(view.cq, ex.store))


@pytest.mark.parametrize("with_inserts", [False, True])
def test_device_delete_pass_uploads_compacted_mirror(with_inserts):
    # device engine: a view that loses rows gets its compacted host
    # mirror uploaded into the same capacity class, before the insert
    # pass scatter-appends after that prefix
    rng = np.random.default_rng(5)
    store = _random_store(rng)
    sess = _session(store, [_chain_cq("q1", 1, 2), _chain_cq("q2", 2, 3)])
    m = ViewMaintainer(sess.executor,
                       MaintenanceConfig(delta_cap=64, insert_engine="device"))
    ex = sess.executor
    assert not m.plans.oracle_vids
    before = {vid: (p.cap, bool(p.overflow))
              for vid, p in ex.device_views.items()}
    cur = ex.store.triples
    dels = cur[np.isin(cur[:, 1], [1, 2])][:48]
    ins = _random_batch(rng, 64) if with_inserts else None
    report = m.apply(Delta.of(ins, dels))
    assert report.removed
    if with_inserts:
        assert set(report.removed) & set(report.appended)
    assert m.telemetry()["delete_uploads"] == len(report.removed)
    for vid, view in ex.state.views.items():
        prel = ex.device_views[vid]
        assert (prel.cap, bool(prel.overflow)) == before[vid]
        m.check_alignment(vid)
        assert (np.asarray(prel.data)[int(prel.n):] == -1).all()
        got = np.unique(ex.extents[vid].rows, axis=0)
        np.testing.assert_array_equal(got, _extent_oracle(view.cq, ex.store))


def test_device_insert_engine_probes_the_tt_index():
    # a store large enough that each predicate's range dwarfs a delta
    # relation: the delta program's joins lower to index probes, and
    # across enrol (insert) and withdraw (delete) batches the device
    # engine's extents equal the host engine's and the oracle's; the
    # serving program reads views only and never probes
    rng = np.random.default_rng(17)
    store = _random_store(rng, n=20000, n_ids=2000)
    cqs = [_chain_cq("q1", 1, 2), _chain_cq("q2", 2, 3)]
    dev, host = _session(store, cqs), _session(store, cqs)
    m_dev = ViewMaintainer(dev.executor, MaintenanceConfig(
        delta_cap=64, insert_engine="device"))
    m_host = ViewMaintainer(host.executor, MaintenanceConfig(
        delta_cap=64, insert_engine="host"))
    for step in range(4):
        ins = _random_batch(rng, 48, n_ids=2000) if step != 1 else None
        cur = dev.executor.store.triples
        dels = (cur[rng.choice(len(cur), 40, replace=False)]
                if step != 0 else None)
        r_dev = m_dev.apply(Delta.of(ins, dels))
        m_host.apply(Delta.of(ins, dels))
        assert r_dev.appended or ins is None
    for vid, view in dev.executor.state.views.items():
        m_dev.check_alignment(vid)
        got = np.unique(dev.executor.extents[vid].rows, axis=0)
        np.testing.assert_array_equal(
            got, np.unique(host.executor.extents[vid].rows, axis=0))
        np.testing.assert_array_equal(
            got, _extent_oracle(view.cq, dev.executor.store))
    t = m_dev.telemetry()
    assert t["insert_engine"] == "device" and t["delta_recompiles"] == 0
    assert t["delta_probe_joins"] > 0
    for q in dev.workload:
        assert dev.answer(q.name) == dev.executor.answer_group_direct(q.name)
    assert dev.executor.workload.telemetry()["probe_joins"] == 0


def test_delete_pass_scans_only_inverted_index_candidates():
    rng = np.random.default_rng(21)
    store = _random_store(rng)
    sess = _session(store, [_chain_cq("q1", 1, 2), _chain_cq("q2", 3, 4)])
    m = ViewMaintainer(sess.executor, MaintenanceConfig())
    ex = sess.executor
    maintained = set(ex.state.views) - m.plans.oracle_vids

    def expected_scans(preds):
        cand = set(m._wild_vids)
        for p in preds:
            cand |= m._pred_vids.get(p, set())
        return len(cand - m.plans.oracle_vids)

    # pred-5 deletes: NO view mentions predicate 5, so only views with a
    # variable-predicate atom can lose a row — everything else is never
    # even iterated (sub-linear in the view count)
    only5 = store.triples[store.triples[:, 1] == 5][:16]
    r5 = m.apply(Delta.of(None, only5))
    assert r5.extents_scanned == expected_scans({5})
    assert r5.extents_scanned < len(maintained)

    # pred-1 deletes: exactly the pred-1 views plus the wild ones
    cur = ex.store.triples
    only1 = cur[cur[:, 1] == 1][:16]
    r1 = m.apply(Delta.of(None, only1))
    assert r1.extents_scanned == expected_scans({1})
    assert m.telemetry()["delete_scans"] == \
        r5.extents_scanned + r1.extents_scanned
    # sub-linear bookkeeping never trades away correctness
    for vid, view in ex.state.views.items():
        got = np.unique(ex.extents[vid].rows, axis=0)
        np.testing.assert_array_equal(got, _extent_oracle(view.cq, ex.store))


# ----------------------------------------------------------------------
# serving: staleness budget, drift retune, measured costs
# ----------------------------------------------------------------------
def test_staleness_budget_bounds_served_lag():
    rng = np.random.default_rng(3)
    store = _random_store(rng)
    sess = _session(store, [_chain_cq("q1", 1, 2)])
    budget = 40
    srv = sess.serve(maintenance=MaintenanceConfig(staleness_budget=budget))
    for _ in range(6):
        srv.submit(inserts=_random_batch(rng, 16))
        srv.answer("q1")
        assert srv.stream.pending_triples <= budget
    assert srv.stats.max_staleness_served <= budget
    assert srv.stats.refreshes >= 1  # the budget forced maintenance
    srv.flush()
    assert srv.stream.pending_triples == 0
    # flushed answers equal the oracle over the final store
    assert srv.answer("q1") == sess.executor.answer_group_direct("q1")


def test_zero_budget_serves_fresh():
    rng = np.random.default_rng(4)
    sess = _session(_random_store(rng), [_chain_cq("q1", 1, 2)])
    srv = sess.serve(maintenance=True)  # default budget: 0
    srv.submit(inserts=_random_batch(rng, 8))
    srv.submit(inserts=_random_batch(rng, 8))
    srv.answer("q1")
    assert srv.stats.max_staleness_served == 0
    assert srv.stats.backlog_triples == 0


def test_drift_triggers_auto_retune():
    rng = np.random.default_rng(5)
    sess = _session(_random_store(rng),
                    [_chain_cq("q1", 1, 2), _chain_cq("q2", 2, 3)])
    srv = sess.serve(maintenance=MaintenanceConfig(
        staleness_budget=0, drift_window=3, drift_rate_factor=2.0,
        drift_min_triples=32))
    for _ in range(4):  # baseline rate: small batches
        srv.submit(inserts=_random_batch(rng, 4))
        srv.answer("q1")
    for _ in range(6):  # drift: 40x the rate, one hot predicate
        b = _random_batch(rng, 160)
        b[:, 1] = 5
        srv.submit(inserts=b)
        srv.answer("q1")
    assert srv.stats.drift_retunes >= 1
    # after the retune the server still answers correctly
    assert srv.answer("q2") == sess.executor.answer_group_direct("q2")


def test_measured_costs_flow_into_retune_objective():
    from repro.core.quality import MaintenanceCostModel, quality
    from repro.core.quality import QualityWeights

    rng = np.random.default_rng(6)
    sess = _session(_random_store(rng), [_chain_cq("q1", 1, 2)])
    sess.ingest(inserts=_random_batch(rng, 32),
                deletes=sess.store.triples[:16])
    assert len(sess.maintenance_costs) >= 1
    # the session's search config now carries the measured model
    assert sess._search_cfg().maint_model is sess.maintenance_costs
    # and a (sufficiently different) measured cost changes the objective
    stats = sess.store.stats
    state = sess.best
    base = quality(state, stats, QualityWeights())
    loaded = MaintenanceCostModel()
    for v in state.views.values():
        loaded.observe(v.cq, 1e4)
    heavy = quality(state, stats, QualityWeights(), loaded)
    assert heavy.total != base.total


def test_rebind_survives_retune_hot_swap():
    rng = np.random.default_rng(8)
    sess = _session(_random_store(rng), [_chain_cq("q1", 1, 2)])
    srv = sess.serve(maintenance=True)
    srv.submit(inserts=_random_batch(rng, 16))
    srv.answer("q1")
    srv.retune_online(add=[_chain_cq("q3", 3, 4)])
    # maintainer rebound to the new view set: streaming keeps working
    srv.submit(inserts=_random_batch(rng, 16))
    assert srv.answer("q3") == sess.executor.answer_group_direct("q3")
    for vid in sess.executor.state.views:
        srv.maintainer.check_alignment(vid)


# ----------------------------------------------------------------------
# delta planner + analyzer
# ----------------------------------------------------------------------
def test_delta_plans_share_isomorphic_leaves():
    from repro.core.state import initial_state

    # q1 and q2 share the (x, P2, y) atom shape: one delta leaf
    state = initial_state([_chain_cq("q1", 1, 2), _chain_cq("q2", 2, 3)])
    plans = build_delta_plans(state)
    assert len(plans.plans) == 4         # 2 views x 2 atoms
    assert len(plans.leaves) == 3        # P1, P2 (shared), P3
    assert not plans.oracle_vids
    assert plans.dag is not None


def test_non_full_projection_goes_to_oracle():
    from repro.core.state import View, initial_state

    x, y, z = Var("x"), Var("y"), Var("z")
    proj = CQ(name="p", head=(x, z),
              atoms=(Atom(x, Const(1), y), Atom(y, Const(2), z)))
    state = initial_state([_chain_cq("q1", 1, 2)])
    vid = max(state.views) + 1
    state.views[vid] = View(vid, proj)
    plans = build_delta_plans(state)
    assert vid in plans.oracle_vids


def test_maintenance_analyzer_static_defaults_clean():
    from repro.analysis import analyze_maintenance

    rng = np.random.default_rng(11)
    sess = _session(_random_store(rng, n=2000),
                    [_chain_cq("q1", 1, 2), _chain_cq("q2", 2, 3)])
    assert analyze_maintenance(sess.best, sess.store.stats) == []


def test_maintenance_analyzer_flags_hazards():
    from types import SimpleNamespace

    from repro.analysis import analyze_maintenance
    from repro.analysis.maintenance_check import _check_delta_cap

    rng = np.random.default_rng(12)
    sess = _session(_random_store(rng, n=400), [_chain_cq("q1", 1, 2)])

    # non-power-of-two delta cap cannot be built through the validated
    # config; the rule still guards hand-rolled configs
    bad = _check_delta_cap(SimpleNamespace(delta_cap=100, expected_batch=8))
    assert any(f.rule == "maint/delta-cap" and f.severity == "error"
               for f in bad)

    # expected batch far above the delta class: chunked-pass warning
    split = analyze_maintenance(
        sess.best, sess.store.stats,
        MaintenanceConfig(delta_cap=128, expected_batch=4096))
    assert any(f.rule == "maint/delta-cap" and f.severity == "warning"
               for f in split)

    # an absurd update rate outruns every headroom envelope
    hot = analyze_maintenance(sess.best, sess.store.stats,
                              update_rate=1e9)
    rules = {f.rule for f in hot}
    assert "maint/extent-headroom" in rules and "maint/tt-headroom" in rules


def test_maintenance_analyzer_live_mode():
    from repro.analysis import analyze_maintenance

    rng = np.random.default_rng(13)
    sess = _session(_random_store(rng, n=2000), [_chain_cq("q1", 1, 2)])
    m = sess.maintainer()
    sess.ingest(inserts=_random_batch(rng, 32))
    assert analyze_maintenance(maintainer=m) == []
    hot = analyze_maintenance(maintainer=m, update_rate=1e9)
    assert any(f.rule == "maint/tt-headroom" for f in hot)


def test_verify_session_covers_maintenance():
    rng = np.random.default_rng(14)
    sess = _session(_random_store(rng, n=2000), [_chain_cq("q1", 1, 2)])
    sess.ingest(inserts=_random_batch(rng, 16))
    report = sess.verify()
    assert report.checked.get("maint_views", 0) >= 1
    assert report.ok
