"""End-to-end wizard + executor + maintenance tests (paper claims 1/3/4)."""
import numpy as np
import pytest

from repro.core.quality import QualityWeights
from repro.core.search import SearchConfig
from repro.core.wizard import WizardConfig, tune
from repro.query import ref_engine as R
from repro.rdf.generator import generate, lubm_workload
from repro.rdf.triples import TripleStore
from repro.views.maintenance import maintain
from repro.views.materializer import materialize_view


@pytest.fixture(scope="module")
def uni():
    return generate(n_universities=1, seed=0, dept_per_univ=2,
                    prof_per_dept=4, stud_per_dept=12, course_per_dept=5)


@pytest.fixture(scope="module")
def report(uni):
    cfg = WizardConfig(search=SearchConfig(strategy="greedy", max_states=400))
    return tune(uni.store, lubm_workload(uni.dictionary), uni.schema,
                uni.type_id, cfg)


def test_wizard_end_to_end_answers(uni, report):
    """Rewritings over materialized views == saturated-store answers."""
    sat = TripleStore(
        uni.schema.saturate_instance(uni.store.triples, uni.type_id),
        uni.dictionary,
    )
    for q in lubm_workload(uni.dictionary):
        got = report.executor.answer_group(q.name)
        want = R.evaluate_cq(q, sat).as_set()
        assert got == want, q.name


def test_wizard_improves_quality(uni, report):
    assert report.result.best_quality.total <= report.initial_quality.total


def test_wizard_without_schema(uni):
    cfg = WizardConfig(search=SearchConfig(strategy="greedy", max_states=200),
                       use_schema=False)
    rep = tune(uni.store, lubm_workload(uni.dictionary), None, None, cfg)
    for q in lubm_workload(uni.dictionary):
        got = rep.executor.answer_group(q.name)
        want = rep.executor.answer_group_direct(q.name)
        assert got == want, q.name


def test_maintenance_incremental_equals_recompute(uni):
    workload = lubm_workload(uni.dictionary)
    view_cq = None
    from repro.core.queries import full_projection

    view_cq = full_projection(workload[1].atoms, name="vq2")
    store = uni.store
    extent = materialize_view(view_cq, store).rows
    rng = np.random.default_rng(3)
    d = uni.dictionary
    takes = d.lookup("ub:takesCourse")
    adv = d.lookup("ub:advisor")
    teach = d.lookup("ub:teacherOf")
    students = store.scan(None, d.lookup("ub:memberOf"), None)[:, 0]
    courses = store.scan(None, takes, None)[:, 2]
    profs = store.scan(None, teach, None)[:, 0]
    for _ in range(8):
        kind = rng.integers(0, 3)
        if kind == 0:
            t = (int(rng.choice(students)), takes, int(rng.choice(courses)))
        elif kind == 1:
            t = (int(rng.choice(students)), adv, int(rng.choice(profs)))
        else:
            t = (int(rng.choice(profs)), teach, int(rng.choice(courses)))
        extent, store, delta = maintain(view_cq, extent, store, t)
        want = materialize_view(view_cq, store).rows
        assert {tuple(r) for r in extent.tolist()} == {tuple(r) for r in want.tolist()}


def test_executor_jax_matches_oracle_per_member(uni, report):
    for name in report.executor.dag.roots:
        got = {tuple(r) for r in report.executor.answer(name).tolist()}
        want = report.executor.answer_direct(name)
        assert got == want, name
