"""The in-program span recorder (repro.trace) and its spans on the serve
and maintenance path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.maintenance import Delta, MaintenanceConfig, UpdateStream


@pytest.fixture(autouse=True)
def recorder():
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


def test_off_returns_one_shared_object_and_records_nothing():
    a, b = trace.span("rdfviews.a"), trace.span("rdfviews.b", new_batch=True)
    assert a is b
    with a:
        with b:
            pass
    trace.record("rdfviews.maint.queued", 0, 10, seq=0)
    assert trace.now_ns() is None
    s = trace.summary()
    assert s["aggregates"] == {} and s["spans"] == [] and s["waits"] == []
    assert s["compiles_by_span"] == {} and s["dropped"] == 0


def test_nesting_gives_parent_ids_and_self_time():
    trace.enable()
    with trace.span("rdfviews.outer"):
        with trace.span("rdfviews.inner"):
            pass
        with trace.span("rdfviews.inner"):
            with trace.span("rdfviews.leaf"):
                pass
    trace.disable()
    s = trace.summary()
    by_name = {}
    for r in s["spans"]:
        by_name.setdefault(r["name"], []).append(r)
    (outer,) = by_name["rdfviews.outer"]
    inner = by_name["rdfviews.inner"]
    (leaf,) = by_name["rdfviews.leaf"]
    assert outer["parent_id"] is None
    assert [r["parent_id"] for r in inner] == [outer["span_id"]] * 2
    assert leaf["parent_id"] == inner[1]["span_id"]
    # children close before their parent and lie inside it
    assert [r["name"] for r in s["spans"]][-1] == "rdfviews.outer"
    for r in inner:
        assert outer["start_ns"] <= r["start_ns"] <= r["end_ns"] \
            <= outer["end_ns"]
    agg = s["aggregates"]
    dur = lambda r: r["end_ns"] - r["start_ns"]
    assert agg["rdfviews.inner"]["count"] == 2
    assert agg["rdfviews.inner"]["total_ns"] == sum(dur(r) for r in inner)
    assert agg["rdfviews.outer"]["self_ns"] == \
        dur(outer) - sum(dur(r) for r in inner)
    assert agg["rdfviews.inner"]["self_ns"] == \
        sum(dur(r) for r in inner) - dur(leaf)
    assert agg["rdfviews.leaf"]["self_ns"] == dur(leaf)


def test_spans_under_one_answer_batch_share_its_batch_id():
    trace.enable()
    with trace.span("rdfviews.loose"):
        pass
    for _ in range(2):
        with trace.span("rdfviews.serve.answer_batch", new_batch=True):
            with trace.span("rdfviews.maint.pass"):
                trace.record("rdfviews.maint.queued", 1, 2, seq=7)
            with trace.span("rdfviews.query.fused_run"):
                pass
    trace.disable()
    s = trace.summary()
    assert s["spans"][0]["batch"] is None
    batches = [r["batch"] for r in s["spans"][1:]]
    assert batches[:3] == [batches[0]] * 3
    assert batches[3:] == [batches[3]] * 3
    assert batches[0] != batches[3] and None not in batches
    assert [(w["batch"], w["seq"]) for w in s["waits"]] == \
        [(batches[0], 7), (batches[3], 7)]


def test_ring_bound_counts_drops_and_aggregates_stay_exact(monkeypatch):
    monkeypatch.setattr(trace, "MAXLEN", 8)
    trace.reset()
    trace.enable()
    for _ in range(20):
        with trace.span("rdfviews.x"):
            pass
    trace.disable()
    s = trace.summary()
    assert len(s["spans"]) == 8
    assert s["dropped"] == 12
    assert s["aggregates"]["rdfviews.x"]["count"] == 20
    trace.reset()
    assert trace.summary()["dropped"] == 0


def test_compiles_are_counted_under_the_innermost_open_span():
    trace.enable()
    with trace.span("rdfviews.outer"):
        with trace.span("rdfviews.compiling"):
            jax.jit(lambda x: x * 3 + 1)(jnp.zeros((7, 13), jnp.int32))
    jax.jit(lambda x: x - 5)(jnp.zeros((11, 3), jnp.int32))
    trace.disable()
    got = trace.summary()["compiles_by_span"]
    assert got.get("rdfviews.compiling", 0) >= 1
    assert got.get(trace.OUTSIDE, 0) >= 1
    assert "rdfviews.outer" not in got
    # disabled: the listener is gone
    jax.jit(lambda x: x - 6)(jnp.zeros((11, 4), jnp.int32))
    assert trace.summary()["compiles_by_span"] == got


def test_update_stream_records_one_wait_per_stamped_batch():
    s = UpdateStream()
    one = np.array([[1, 2, 3]], np.int32)
    s.push(Delta.of(one, None))            # tracing off: no stamp
    trace.enable()
    s.push(Delta.of(one + 1, None))
    s.push(Delta.of(one + 2, None))
    s.pop()                                 # the unstamped batch
    s.applied()
    s.push_front(s.pop())                   # its pass failed: no record
    assert trace.summary()["waits"] == []
    s.coalesce()
    s.applied()
    trace.disable()
    waits = trace.summary()["waits"]
    assert [w["seq"] for w in waits] == [1, 2]
    assert all(w["name"] == "rdfviews.maint.queued" for w in waits)
    assert all(w["end_ns"] >= w["start_ns"] for w in waits)
    # the requeued batch waited until the pass that applied it
    assert waits[0]["end_ns"] == waits[1]["end_ns"]


def _served(chaos=None):
    from repro.api import TuningSession
    from repro.rdf.generator import generate, lubm_workload

    uni = generate(n_universities=1, seed=0, dept_per_univ=2,
                   prof_per_dept=3, stud_per_dept=10, course_per_dept=4)
    sess = TuningSession(uni.store, workload=lubm_workload(uni.dictionary),
                         type_id=uni.dictionary.lookup("rdf:type"))
    sess.retune()
    sess.apply()
    srv = sess.serve(maintenance=MaintenanceConfig(insert_engine="host"),
                     chaos=chaos)
    return uni, srv


def test_serve_run_span_tree():
    uni, srv = _served()
    names = sorted(srv.executor.groups)
    srv.answer_batch(names)
    tt = uni.store.triples
    takes = uni.dictionary.lookup("ub:takesCourse")
    subj = int(tt[tt[:, 1] == takes][0, 0])
    mine = tt[tt[:, 0] == subj]
    clone = mine.copy()
    clone[:, 0] = int(tt.max()) + 1

    trace.enable()
    srv.submit(deletes=mine)
    srv.submit(inserts=clone)
    out = srv.answer_batch(names)
    trace.disable()
    assert out == [srv.executor.answer_group_direct(n) for n in names]

    s = trace.summary()
    assert s["dropped"] == 0
    spans = s["spans"]
    by_id = {r["span_id"]: r for r in spans}

    def named(name):
        return [r for r in spans if r["name"] == name]

    (batch,) = named("rdfviews.serve.answer_batch")
    (pas,) = named("rdfviews.maint.pass")
    assert pas["parent_id"] == batch["span_id"]
    for phase in ("store", "delete", "tt_upload", "insert"):
        (r,) = named(f"rdfviews.maint.{phase}")
        assert r["parent_id"] == pas["span_id"], phase
    (ins,) = named("rdfviews.maint.insert")
    for child in ("delta_program", "append"):
        rs = named(f"rdfviews.maint.{child}")
        assert rs and all(r["parent_id"] == ins["span_id"] for r in rs)
    for name in ("rdfviews.serve.integrity", "rdfviews.query.fused_run",
                 "rdfviews.serve.assemble"):
        (r,) = named(name)
        assert r["parent_id"] == batch["span_id"], name
    (run,) = named("rdfviews.query.fused_run")
    buckets = [r for r in spans if r["name"].startswith(
        "rdfviews.query.bucket.")]
    assert buckets and all(r["parent_id"] == run["span_id"]
                           for r in buckets)
    assert any(by_id.get(r["parent_id"], {}).get("name")
               == "rdfviews.query.fused_run"
               for r in named("rdfviews.query.to_numpy"))
    # one queued wait per pushed batch, applied by this batch
    assert [w["seq"] for w in s["waits"]] == [0, 1]
    assert {w["batch"] for w in s["waits"]} == {batch["batch"]}
    assert {r["batch"] for r in spans} == {batch["batch"]}
    assert all(r["name"].startswith("rdfviews.") for r in spans)
    assert s["aggregates"]["rdfviews.maint.pass"]["count"] == 1


def test_a_failed_pass_leaves_the_wait_to_the_pass_that_applies_it():
    from repro.serve.chaos import FaultInjector

    chaos = FaultInjector()
    uni, srv = _served(chaos)
    names = sorted(srv.executor.groups)
    srv.answer_batch(names)
    tt = uni.store.triples
    extra = tt[:1].copy()
    extra[:, 0] = int(tt.max()) + 1

    trace.enable()
    srv.submit(inserts=extra)
    chaos.arm("maintenance_apply", count=1)
    srv.answer_batch(names)                 # the pass fails: requeued
    assert srv.stats.maintenance_failures == 1
    assert trace.summary()["waits"] == []
    srv.answer_batch(names)                 # the requeued batch applies
    trace.disable()
    s = trace.summary()
    (wait,) = s["waits"]
    first, second = [r for r in s["spans"]
                     if r["name"] == "rdfviews.serve.answer_batch"]
    assert wait["seq"] == 0 and wait["batch"] == second["batch"]
    assert second["start_ns"] <= wait["end_ns"] <= second["end_ns"]
    assert wait["start_ns"] < first["start_ns"]
