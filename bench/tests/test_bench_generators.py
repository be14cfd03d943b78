"""The copied LUBM generator and the traffic schedule."""
import copy
import os

import numpy as np
import pytest

from bench import schedule as S
from bench.generators import lubm as G
from bench.harness import BENCH, Cell, load_json
from bench.references.lubm import Reference, pack_rows


@pytest.fixture(scope="module")
def cfg():
    c = copy.deepcopy(Cell("lubm10.write-heavy").cfg)
    c["universities"] = 2
    return c


@pytest.fixture(scope="module")
def uni(cfg):
    return G.generate(cfg, [7, 0], pool=100)


def _count(uni, p, o=None):
    t = uni.triples
    m = t[:, 1] == uni.vocab[p]
    if o is not None:
        m &= t[:, 2] == uni.vocab[o]
    return t[m]


def _within(values, lohi):
    values = np.asarray(values)
    return len(values) and values.min() >= lohi[0] and values.max() <= lohi[1]


def _per(rows, col, ids):
    """How many of `rows` name each of `ids` in column `col`."""
    got = dict(zip(*np.unique(rows[:, col], return_counts=True)))
    return np.asarray([got.get(int(i), 0) for i in ids])


def test_per_department_counts_match_the_configuration(cfg, uni):
    prof = cfg["profile"]
    T = "rdf:type"
    D = uni.n_depts
    assert len(_count(uni, T, "ub:University")) == cfg["universities"]
    assert len(_count(uni, T, "ub:Department")) == D
    lo, hi = prof["departments_per_university"]
    assert lo * cfg["universities"] <= D <= hi * cfg["universities"]
    works = np.r_[_count(uni, "ub:worksFor"), _count(uni, "ub:headOf")]
    assert len(_count(uni, "ub:headOf")) == D
    faculty = _per(works, 2, uni.dept_ids)
    for rank in G.RANKS:
        ids = _count(uni, T, rank)[:, 0]
        in_dept = _per(works[np.isin(works[:, 0], ids)], 2, uni.dept_ids)
        assert _within(in_dept, prof["faculty"][rank])
        pubs = _per(_count(uni, "ub:publicationAuthor"), 2, ids)
        assert _within(pubs, prof["publications"][rank])
    members = _count(uni, "ub:memberOf")
    for kind, key in (("ub:UndergraduateStudent", "undergraduates_per_faculty"),
                      ("ub:GraduateStudent", "graduates_per_faculty")):
        ids = _count(uni, T, kind)[:, 0]
        per = _per(members[np.isin(members[:, 0], ids)], 2, uni.dept_ids)
        assert (per % faculty == 0).all() and _within(per // faculty,
                                                      prof[key])
    # every course has one teacher, each teacher 1-2 of each kind
    taught = _count(uni, "ub:teacherOf")
    courses = np.r_[_count(uni, T, "ub:Course")[:, 0],
                    _count(uni, T, "ub:GraduateCourse")[:, 0]]
    assert np.array_equal(np.sort(taught[:, 2]), np.sort(courses))
    # distinct courses of the student's own kind, in the profile's ranges
    takes = _count(uni, "ub:takesCourse")
    for kind, key in (("ub:UndergraduateStudent", "courses_per_undergraduate"),
                      ("ub:GraduateStudent", "courses_per_graduate")):
        ids = _count(uni, T, kind)[:, 0]
        assert _within(_per(takes, 0, ids), prof[key])
    grad_courses = _count(uni, T, "ub:GraduateCourse")[:, 0]
    grads = _count(uni, T, "ub:GraduateStudent")[:, 0]
    assert np.array_equal(np.isin(takes[:, 0], grads),
                          np.isin(takes[:, 2], grad_courses))
    assert _within(_per(_count(uni, "ub:advisor"), 0, grads), (1, 1))
    assert len(_count(uni, T, "ub:TeachingAssistant")) == \
        len(_count(uni, "ub:teachingAssistantOf")) > 0
    assert len(_count(uni, T, "ub:ResearchAssistant")) > 0
    # every property of the ontology the data uses appears
    assert set(np.unique(uni.triples[:, 1])) == \
        {uni.vocab[p] for p in ["rdf:type", *G.PROPS]} - \
        {uni.vocab["ub:degreeFrom"]}
    assert len(np.unique(uni.triples, axis=0)) == len(uni.triples)
    assert uni.triples.max() < uni.next_id <= uni.id_limit <= G.ID_LIMIT


def test_lubm10_comes_near_its_published_size():
    c = Cell("lubm10.write-heavy").cfg
    assert c["universities"] == 10
    n = len(G.generate(c, [1, 0], pool=1).triples)
    # LUBM(10,0): about 1.32M triples (Guo, Pan & Heflin 2005)
    assert abs(n - 1.32e6) / 1.32e6 < 0.06


def test_store_sizes_are_the_same_for_every_seed(cfg, uni):
    other = G.generate(cfg, [8, 0], pool=100)
    assert len(other.triples) == len(uni.triples)
    assert np.array_equal(np.bincount(other.triples[:, 1]),
                          np.bincount(uni.triples[:, 1]))
    assert not np.array_equal(other.triples, uni.triples)


def _mix(name):
    return load_json(os.path.join(BENCH, "traffic", name + ".json"))


def _schedule(cfg, seed, mix):
    u = G.generate(cfg, [seed, 0],
                   pool=S.enrolment_ids_needed(mix, 20.0, 10.0))
    w = {q["name"]: q["weight"] for q in cfg["queries"]}
    return S.build(mix, 20.0, 10.0, seed, u, G, w)


@pytest.mark.parametrize("traffic", ["read-mostly", "write-heavy"])
def test_schedule_is_a_pure_function_of_the_seed(cfg, traffic):
    mix = _mix(traffic)
    seed = 2**31 + 12345
    a, b = _schedule(cfg, seed, mix), _schedule(cfg, seed, mix)
    assert [e.due for e in a.events] == [e.due for e in b.events]
    assert [r.name for r in a.reads] == [r.name for r in b.reads]
    for x, y in zip(a.batches, b.batches):
        assert np.array_equal(x.inserts, y.inserts)
        assert np.array_equal(x.deletes, y.deletes)
    c = _schedule(cfg, seed + 1, mix)
    # another seed: the same arrivals, kinds and templates, and update
    # operations of the same kinds and sizes on other students
    assert [e.due for e in c.events] == [e.due for e in a.events]
    assert [r.name for r in c.reads] == [r.name for r in a.reads]
    assert any(not np.array_equal(x.inserts, y.inserts)
               or not np.array_equal(x.deletes, y.deletes)
               for x, y in zip(a.batches, c.batches))
    gaps = np.diff([0.0] + [e.due for e in c.events])
    assert len(gaps) == len(c.events) and (gaps > 0).all()
    assert c.events[-1].due < 10.0


def test_counts_follow_the_mix():
    mix = _mix("read-mostly")
    n_reads, n_batches, n_ops = S.counts(mix, 80.0, 10.0)
    assert (n_reads, n_batches, n_ops) == (760, 10, 40)
    names = S.template_counts({"a": 10, "b": 5, "c": 1}, 32)
    assert (names.count("a"), names.count("b"), names.count("c")) == \
        (20, 10, 2)


def test_update_operations_enrol_and_withdraw_whole_students(cfg, uni):
    rng = np.random.default_rng(3)
    src = G.UpdateSource(uni, rng, 40, 0.5)
    ops = [src.op() for _ in range(40)]
    enrols = [i for i, d in ops if len(i)]
    withdrawals = [d for i, d in ops if len(d)]
    assert len(enrols) == len(withdrawals) == 20
    t = uni.triples
    present = set(map(tuple, t.tolist()))
    for d in withdrawals:
        sid = int(d[0, 0])
        # every triple about the student, and nothing else
        about = t[(t[:, 0] == sid) | (t[:, 2] == sid)]
        assert sorted(map(tuple, d.tolist())) == \
            sorted(map(tuple, about.tolist()))
        assert all(tuple(r) in present for r in d.tolist())
    for i in enrols:
        assert 7 <= len(i) <= 10
        assert len(np.unique(i[:, 0])) == 1 and i[0, 0] >= uni.next_id
        assert (i[:, 1] == uni.vocab["ub:takesCourse"]).sum() >= 1
    # warm-up leaves the store as it was
    (ins, _), (_, dels) = G.warmup_batches(uni, rng, 8)
    assert np.array_equal(ins, dels) and ins[:, 0].min() >= uni.next_id
    assert ins.max() < uni.id_limit


def test_a_batch_nets_an_enrolment_withdrawn_in_it():
    row = np.asarray([[1, 2, 3]], np.int32)
    empty = np.zeros((0, 3), np.int32)
    ins, dels = S._net([(row, empty), (empty, row)])
    assert len(ins) == 0 and len(dels) == 0


def test_reference_entails_and_joins(cfg, uni):
    ref = Reference(uni.triples, cfg["queries"])
    V = uni.vocab
    # q4 asks for Faculty: every faculty member, by subclass and by the
    # domain of teacherOf, with its department (headOf is a worksFor)
    q4 = ref.answer("q4")
    assert len(q4) == len(_count(uni, "ub:worksFor")) + \
        len(_count(uni, "ub:headOf"))
    q1 = ref.answer("q1")
    grads = _count(uni, "rdf:type", "ub:GraduateStudent")[:, 0]
    takes = _count(uni, "ub:takesCourse")
    assert len(q1) == np.isin(takes[:, 0], grads).sum()
    # an update changes the answer; a withdrawal takes it back
    sid = uni.next_id
    course = int(uni.grad_courses[0][1])
    new = G.student_triples(uni, G.Student(
        sid, sid + 1, sid + 2, 0, True, [course],
        int(uni.professors[0][2]), int(uni.degree_univs[5])))
    ref.apply(new, np.zeros((0, 3), np.int32))
    assert len(ref.answer("q1")) == len(q1) + 1
    assert pack_rows([[sid, course]])[0] in ref.answer("q1")
    ref.apply(np.zeros((0, 3), np.int32), new)
    assert np.array_equal(ref.answer("q1"), q1)
    assert V["rdf:type"] == 0
