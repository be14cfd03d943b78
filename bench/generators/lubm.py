"""LUBM's university data, its update operations, and the ids the
harness needs.

A vectorised generator after LUBM's own (UBA 1.7; Guo, Pan & Heflin,
J. Web Semantics 3(2), 2005).  It writes every class and property UBA
writes, at UBA's per-department ranges, which the configuration states
under `profile`:

  * 15-25 departments a university; per department 7-10 full, 10-14
    associate and 8-11 assistant professors and 5-7 lecturers, 8-14
    undergraduates and 3-4 graduates per faculty member, 10-20 research
    groups (`rdf:type`, `subOrganizationOf` the department);
  * a faculty member: its class, `name`, `emailAddress`, `telephone`,
    `researchInterest`, `worksFor` the department (the chair, a full
    professor, `headOf` it instead), `undergraduateDegreeFrom`,
    `mastersDegreeFrom` and `doctoralDegreeFrom` a university, and
    `teacherOf` 1-2 courses and 1-2 graduate courses, each course (its
    class and `name`) taught by that member alone;
  * publications (`rdf:type`, `name`, `publicationAuthor`): 15-20 a full,
    10-18 an associate, 5-10 an assistant professor, 0-5 a lecturer; a
    graduate co-authors 0-5 of its department's;
  * an undergraduate: its class, `name`, `emailAddress`, `telephone`,
    `memberOf` the department, `takesCourse` 2-4 courses, and one in 5
    an `advisor`, a professor of the department;
  * a graduate: the same with 1-3 graduate courses, always an advisor,
    and `undergraduateDegreeFrom` a university; one in 4-5 graduates is
    a `TeachingAssistant` (`teachingAssistantOf` a course) and one in
    3-4 a `ResearchAssistant`, never both.

Degrees name one of 1000 universities, of which the first are the
generated ones.  Every count is drawn from one fixed stream, the same
for every seed, as LUBM(N, 0) names its data set by UBA's seed 0 (not
UBA's Java stream, so totals come near the published ones, not equal);
the run's seed draws the pairings: which courses, advisors, degrees,
chairs, co-authored publications and assistants.  So every seed has the
same number of triples of each property.

Ids: 0 is rdf:type, then the classes and properties, then the
entities, the literals (names shared across departments as UBA's
strings are; one e-mail address per person; one telephone literal;
research interests), then a pool of ids for students that updates
enrol.  Every id stays below 2**21, the packing width of the program's
triple keys and of the reference's.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

ID_LIMIT = 1 << 21
IDS_PER_ENROLMENT = 3          # the student, its name, its e-mail address

# The RDFS part of the univ-bench ontology for the terms the data uses.
CLASSES = [
    "ub:Organization", "ub:University", "ub:Department", "ub:ResearchGroup",
    "ub:Person", "ub:Employee", "ub:Faculty", "ub:Professor",
    "ub:FullProfessor", "ub:AssociateProfessor", "ub:AssistantProfessor",
    "ub:Lecturer", "ub:Student", "ub:UndergraduateStudent",
    "ub:GraduateStudent", "ub:TeachingAssistant", "ub:ResearchAssistant",
    "ub:Work", "ub:Course", "ub:GraduateCourse", "ub:Publication",
]
SUBCLASS = [
    ("ub:University", "ub:Organization"),
    ("ub:Department", "ub:Organization"),
    ("ub:ResearchGroup", "ub:Organization"),
    ("ub:Employee", "ub:Person"),
    ("ub:Faculty", "ub:Employee"),
    ("ub:Professor", "ub:Faculty"),
    ("ub:FullProfessor", "ub:Professor"),
    ("ub:AssociateProfessor", "ub:Professor"),
    ("ub:AssistantProfessor", "ub:Professor"),
    ("ub:Lecturer", "ub:Faculty"),
    ("ub:Student", "ub:Person"),
    ("ub:UndergraduateStudent", "ub:Student"),
    ("ub:GraduateStudent", "ub:Person"),
    ("ub:TeachingAssistant", "ub:Person"),
    ("ub:ResearchAssistant", "ub:Person"),
    ("ub:Course", "ub:Work"),
    ("ub:GraduateCourse", "ub:Course"),
]
PROPS = {  # property: (domain, range); None where the ontology has none
    "ub:name": (None, None),
    "ub:emailAddress": ("ub:Person", None),
    "ub:telephone": ("ub:Person", None),
    "ub:researchInterest": (None, None),
    "ub:memberOf": (None, None),
    "ub:worksFor": (None, None),
    "ub:headOf": (None, None),
    "ub:subOrganizationOf": ("ub:Organization", "ub:Organization"),
    "ub:teacherOf": ("ub:Faculty", "ub:Course"),
    "ub:takesCourse": (None, None),
    "ub:advisor": ("ub:Person", "ub:Professor"),
    "ub:teachingAssistantOf": ("ub:TeachingAssistant", "ub:Course"),
    "ub:publicationAuthor": ("ub:Publication", "ub:Person"),
    "ub:degreeFrom": ("ub:Person", "ub:University"),
    "ub:undergraduateDegreeFrom": ("ub:Person", "ub:University"),
    "ub:mastersDegreeFrom": ("ub:Person", "ub:University"),
    "ub:doctoralDegreeFrom": ("ub:Person", "ub:University"),
}
SUBPROP = [
    ("ub:headOf", "ub:worksFor"),
    ("ub:worksFor", "ub:memberOf"),
    ("ub:undergraduateDegreeFrom", "ub:degreeFrom"),
    ("ub:mastersDegreeFrom", "ub:degreeFrom"),
    ("ub:doctoralDegreeFrom", "ub:degreeFrom"),
]
RANKS = ["ub:FullProfessor", "ub:AssociateProfessor", "ub:AssistantProfessor",
         "ub:Lecturer"]
# names are literals shared across departments, one run per kind
NAME_KINDS = RANKS + ["ub:UndergraduateStudent", "ub:GraduateStudent",
                      "ub:Course", "ub:GraduateCourse", "ub:Publication",
                      "ub:Department", "ub:University"]


def vocabulary() -> dict[str, int]:
    """Ids of rdf:type, the classes and the properties."""
    names = ["rdf:type"] + CLASSES + list(PROPS)
    return {n: i for i, n in enumerate(names)}


@dataclass
class Universe:
    """The generated store and what update operations need of it."""

    triples: np.ndarray        # (N, 3) int32, distinct rows
    vocab: dict[str, int]
    dept_ids: np.ndarray       # (D,)
    courses: list              # per department: undergraduate course ids
    grad_courses: list         # per department: graduate course ids
    professors: list           # per department: professor ids
    degree_univs: np.ndarray   # universities a degree may name
    telephone: int             # the one telephone literal
    students: np.ndarray       # (S,) ids of the generated students
    grad_share: float          # graduates among the generated students
    profile: dict
    next_id: int               # first id of the enrolment pool
    id_limit: int              # ids are below this

    @property
    def n_depts(self) -> int:
        return len(self.dept_ids)

    def __post_init__(self):
        t = self.triples
        self._by_s = np.argsort(t[:, 0], kind="stable")
        self._s = t[self._by_s, 0]
        auth = t[t[:, 1] == self.vocab["ub:publicationAuthor"]]
        self._auth = auth[np.argsort(auth[:, 2], kind="stable")]

    def rows_of(self, sid: int) -> np.ndarray:
        """Every generated triple about `sid`: those it is the subject
        of, and the publications it co-authors."""
        lo, hi = np.searchsorted(self._s, [sid, sid + 1])
        alo, ahi = np.searchsorted(self._auth[:, 2], [sid, sid + 1])
        return np.concatenate([self.triples[self._by_s[lo:hi]],
                               self._auth[alo:ahi]])


def _range(rng, lohi, size) -> np.ndarray:
    """UBA's `_getRandomFromRange`: uniform in [lo, hi], both included."""
    lo, hi = lohi
    return rng.integers(lo, hi + 1, size=size)


def _distinct_draws(rng, pools, k: int) -> np.ndarray:
    """(n, k) indices, row i into range(pools[i]), distinct within each
    row; each pool holds at least k."""
    pools = np.asarray(pools, np.int64)
    out = np.empty((len(pools), k), np.int64)
    for j in range(k):
        x = rng.integers(0, pools - j)
        # step over the indices drawn before, lowest first
        prev = np.sort(out[:, :j], axis=1)
        for c in range(j):
            x = x + (x >= prev[:, c])
        out[:, j] = x
    return out


def _index_in_group(group: np.ndarray) -> np.ndarray:
    """Position of each element within its run of equal, sorted values."""
    n = len(group)
    if n == 0:
        return np.zeros(0, np.int64)
    start = np.r_[0, np.flatnonzero(np.diff(group)) + 1]
    lens = np.diff(np.r_[start, n])
    return np.arange(n) - np.repeat(start, lens)


def _offsets(counts: np.ndarray) -> np.ndarray:
    return np.r_[0, np.cumsum(counts)[:-1]].astype(np.int64)


def generate(cfg: dict, seed, pool: int) -> Universe:
    """The store of configuration `cfg`, its pairings drawn from `seed`,
    with ids set aside for `pool` students that updates enrol."""
    prof = cfg["profile"]
    size = np.random.default_rng(0)            # the counts: fixed
    rng = np.random.default_rng(seed)          # the pairings
    V = vocabulary()
    T = V["rdf:type"]
    U = int(cfg["universities"])
    n_univ = int(prof["universities_named_by_degrees"])
    if U > n_univ:
        raise ValueError("more universities than degrees may name")

    # -- counts, the same for every seed ----------------------------------
    dpu = _range(size, prof["departments_per_university"], U)
    D = int(dpu.sum())
    nf = np.stack([_range(size, prof["faculty"][r], D) for r in RANKS], 1)
    F_d = nf.sum(1)
    nug_d = F_d * _range(size, prof["undergraduates_per_faculty"], D)
    ngr_d = F_d * _range(size, prof["graduates_per_faculty"], D)
    nrg_d = _range(size, prof["research_groups"], D)
    n_ta_d = ngr_d // _range(size, prof["graduates_per_teaching_assistant"], D)
    n_ra_d = ngr_d // _range(size, prof["graduates_per_research_assistant"], D)
    F, NU, NG = int(F_d.sum()), int(nug_d.sum()), int(ngr_d.sum())
    f_dept = np.repeat(np.arange(D), F_d)
    f_rank = np.concatenate([np.repeat(np.arange(4), nf[d]) for d in range(D)])
    n_crs = _range(size, prof["courses_per_faculty"], F)
    n_gcrs = _range(size, prof["graduate_courses_per_faculty"], F)
    n_pub = np.zeros(F, np.int64)
    for r, rank in enumerate(RANKS):
        m = f_rank == r
        n_pub[m] = _range(size, prof["publications"][rank], int(m.sum()))
    u_dept = np.repeat(np.arange(D), nug_d)
    g_dept = np.repeat(np.arange(D), ngr_d)
    u_ncrs = _range(size, prof["courses_per_undergraduate"], NU)
    g_ncrs = _range(size, prof["courses_per_graduate"], NG)
    u_adv = size.integers(0, int(prof["undergraduates_per_advisee"]),
                          NU) == 0
    g_npub = _range(size, prof["publications"]["ub:GraduateStudent"], NG)
    n_interest = int(prof["research_interests"])

    # -- ids ---------------------------------------------------------------
    nid = len(V)

    def block(n: int) -> np.ndarray:
        nonlocal nid
        ids = np.arange(nid, nid + n, dtype=np.int64)
        nid += n
        return ids

    univ = block(n_univ)                  # the first U are generated
    dept = block(D)
    rgroup = block(int(nrg_d.sum()))
    fac = block(F)
    crs = block(int(n_crs.sum()))
    gcrs = block(int(n_gcrs.sum()))
    ug = block(NU)
    gr = block(NG)
    pub = block(int(n_pub.sum()))
    f_idx = _index_in_group(f_dept * 4 + f_rank)
    c_teacher = np.repeat(np.arange(F), n_crs)
    gc_teacher = np.repeat(np.arange(F), n_gcrs)
    c_dept, gc_dept = f_dept[c_teacher], f_dept[gc_teacher]
    p_author = np.repeat(np.arange(F), n_pub)
    idx_of = {
        "ub:UndergraduateStudent": _index_in_group(u_dept),
        "ub:GraduateStudent": _index_in_group(g_dept),
        "ub:Course": _index_in_group(c_dept),
        "ub:GraduateCourse": _index_in_group(gc_dept),
        "ub:Publication": _index_in_group(p_author),
        "ub:Department": _index_in_group(np.repeat(np.arange(U), dpu)),
        "ub:University": np.arange(U),
    }
    name_base = {}
    for r, rank in enumerate(RANKS):
        m = f_rank == r
        name_base[rank] = block(int(f_idx[m].max()) + 1)[0]
    for kind in NAME_KINDS[4:]:
        name_base[kind] = block(int(idx_of[kind].max()) + 1)[0]
    f_name = np.asarray([name_base[RANKS[r]] for r in range(4)])[f_rank] \
        + f_idx
    email_f, email_u, email_g = block(F), block(NU), block(NG)
    telephone = int(block(1)[0])
    interest = block(n_interest)
    next_id = nid
    id_limit = nid + IDS_PER_ENROLMENT * pool
    if id_limit > ID_LIMIT:
        raise ValueError(f"{id_limit} ids do not fit in 21 bits")

    parts: list[np.ndarray] = []

    def emit(s, p, o) -> None:
        s = np.asarray(s, np.int64).ravel()
        o = np.broadcast_to(np.asarray(o, np.int64), s.shape).ravel()
        parts.append(np.stack([s, np.full_like(s, V[p] if isinstance(p, str)
                                               else p), o], axis=1))

    def name(ids, kind) -> None:
        emit(ids, "ub:name", name_base[kind] + idx_of[kind])

    def persons(ids, email) -> None:
        emit(ids, "ub:emailAddress", email)
        emit(ids, "ub:telephone", telephone)

    univ_of_dept = univ[np.repeat(np.arange(U), dpu)]
    emit(univ[:U], T, V["ub:University"])
    name(univ[:U], "ub:University")
    emit(dept, T, V["ub:Department"])
    name(dept, "ub:Department")
    emit(dept, "ub:subOrganizationOf", univ_of_dept)
    emit(rgroup, T, V["ub:ResearchGroup"])
    emit(rgroup, "ub:subOrganizationOf", dept[np.repeat(np.arange(D), nrg_d)])

    # faculty; the chair of each department is one of its full professors
    f_off = _offsets(F_d)
    chair = f_off + rng.integers(0, nf[:, 0])
    is_chair = np.zeros(F, bool)
    is_chair[chair] = True
    emit(fac, T, np.asarray([V[r] for r in RANKS])[f_rank])
    emit(fac, "ub:name", f_name)
    persons(fac, email_f)
    emit(fac, "ub:researchInterest", interest[rng.integers(0, n_interest, F)])
    emit(fac[~is_chair], "ub:worksFor", dept[f_dept[~is_chair]])
    emit(fac[chair], "ub:headOf", dept)
    for p in ("ub:undergraduateDegreeFrom", "ub:mastersDegreeFrom",
              "ub:doctoralDegreeFrom"):
        emit(fac, p, univ[rng.integers(0, n_univ, F)])
    emit(fac[c_teacher], "ub:teacherOf", crs)
    emit(fac[gc_teacher], "ub:teacherOf", gcrs)
    emit(crs, T, V["ub:Course"])
    name(crs, "ub:Course")
    emit(gcrs, T, V["ub:GraduateCourse"])
    name(gcrs, "ub:GraduateCourse")
    emit(pub, T, V["ub:Publication"])
    name(pub, "ub:Publication")
    emit(pub, "ub:publicationAuthor", fac[p_author])

    # per department: its courses, graduate courses, professors, papers
    c_cnt = np.bincount(c_dept, minlength=D)
    gc_cnt = np.bincount(gc_dept, minlength=D)
    p_cnt = np.bincount(f_dept[p_author], minlength=D)
    c_off, gc_off, p_off = _offsets(c_cnt), _offsets(gc_cnt), _offsets(p_cnt)
    n_prof = nf[:, :3].sum(1)

    def takes(ids, dep, ncrs, cnt, off, pool_ids, kmax) -> np.ndarray:
        pick = _distinct_draws(rng, cnt[dep], kmax)
        got = pool_ids[off[dep][:, None] + pick]
        keep = np.arange(kmax)[None, :] < ncrs[:, None]
        emit(np.broadcast_to(ids[:, None], got.shape)[keep],
             "ub:takesCourse", got[keep])
        return np.where(keep, got, -1)

    def advisor(dep) -> np.ndarray:
        return fac[f_off[dep] + rng.integers(0, n_prof[dep])]

    # undergraduates
    emit(ug, T, V["ub:UndergraduateStudent"])
    name(ug, "ub:UndergraduateStudent")
    persons(ug, email_u)
    emit(ug, "ub:memberOf", dept[u_dept])
    takes(ug, u_dept, u_ncrs, c_cnt, c_off, crs,
          int(prof["courses_per_undergraduate"][1]))
    emit(ug[u_adv], "ub:advisor", advisor(u_dept[u_adv]))

    # graduates, their assistantships and the papers they co-author
    emit(gr, T, V["ub:GraduateStudent"])
    name(gr, "ub:GraduateStudent")
    persons(gr, email_g)
    emit(gr, "ub:memberOf", dept[g_dept])
    takes(gr, g_dept, g_ncrs, gc_cnt, gc_off, gcrs,
          int(prof["courses_per_graduate"][1]))
    emit(gr, "ub:advisor", advisor(g_dept))
    emit(gr, "ub:undergraduateDegreeFrom", univ[rng.integers(0, n_univ, NG)])
    order = np.lexsort((rng.random(NG), g_dept))
    rank = np.empty(NG, np.int64)
    rank[order] = _index_in_group(g_dept[order])
    ta = rank < n_ta_d[g_dept]
    ra = ~ta & (rank < (n_ta_d + n_ra_d)[g_dept])
    emit(gr[ta], T, V["ub:TeachingAssistant"])
    ta_dept = g_dept[ta]
    emit(gr[ta], "ub:teachingAssistantOf",
         crs[c_off[ta_dept] + rng.integers(0, c_cnt[ta_dept])])
    emit(gr[ra], T, V["ub:ResearchAssistant"])
    kmax = int(prof["publications"]["ub:GraduateStudent"][1])
    pick = _distinct_draws(rng, p_cnt[g_dept], kmax)
    papers = pub[p_off[g_dept][:, None] + pick]
    keep = np.arange(kmax)[None, :] < g_npub[:, None]
    emit(papers[keep], "ub:publicationAuthor",
         np.broadcast_to(gr[:, None], papers.shape)[keep])

    triples = np.concatenate(parts).astype(np.int32)
    return Universe(
        triples=triples, vocab=V, dept_ids=dept,
        courses=np.split(crs, np.cumsum(c_cnt)[:-1]),
        grad_courses=np.split(gcrs, np.cumsum(gc_cnt)[:-1]),
        professors=[fac[f_off[d]:f_off[d] + n_prof[d]] for d in range(D)],
        degree_univs=univ, telephone=telephone,
        students=np.r_[ug, gr], grad_share=NG / (NU + NG), profile=prof,
        next_id=next_id, id_limit=id_limit)


@dataclass
class Student:
    """One student as an enrolment makes it."""

    sid: int
    name: int
    email: int
    dept: int
    grad: bool
    courses: list
    advisor: int               # -1: none
    degree: int                # -1: none (undergraduates)


def student_triples(u: Universe, st: Student) -> np.ndarray:
    """Every triple the generator makes for an enrolled student."""
    V = u.vocab
    kind = "ub:GraduateStudent" if st.grad else "ub:UndergraduateStudent"
    rows = [(st.sid, V["rdf:type"], V[kind]),
            (st.sid, V["ub:name"], st.name),
            (st.sid, V["ub:emailAddress"], st.email),
            (st.sid, V["ub:telephone"], u.telephone),
            (st.sid, V["ub:memberOf"], int(u.dept_ids[st.dept]))]
    rows += [(st.sid, V["ub:takesCourse"], int(c)) for c in st.courses]
    if st.advisor >= 0:
        rows.append((st.sid, V["ub:advisor"], st.advisor))
    if st.degree >= 0:
        rows.append((st.sid, V["ub:undergraduateDegreeFrom"], st.degree))
    return np.asarray(rows, np.int32)


def _new_student(u: Universe, rng, ids, grad: bool, k: int,
                 advised: bool) -> Student:
    """A student for an enrolment: department, courses, advisor and
    degree from `rng`; kind, course count and whether an undergraduate
    has an advisor as given."""
    dept = int(rng.integers(0, u.n_depts))
    pool = u.grad_courses[dept] if grad else u.courses[dept]
    crs = pool[_distinct_draws(rng, [len(pool)], k)[0]].tolist()
    profs = u.professors[dept]
    adv = int(profs[rng.integers(0, len(profs))]) if grad or advised else -1
    deg = int(u.degree_univs[rng.integers(0, len(u.degree_univs))]) \
        if grad else -1
    sid, nm, em = (int(i) for i in ids)
    return Student(sid, nm, em, dept, grad, crs, adv, deg)


def _shapes(order, n: int, u: Universe) -> tuple:
    """Graduate flags, course counts and advisor flags of `n` enrolments:
    fixed multisets in the proportions of the profile, shuffled."""
    prof = u.profile
    n = max(n, 1)
    n_grad = int(round(n * u.grad_share))
    grads = order.permutation(np.arange(n) < n_grad)

    def spread(lohi, m):
        return np.resize(np.arange(lohi[0], lohi[1] + 1), m)

    k = np.empty(n, np.int64)
    k[grads] = order.permutation(spread(prof["courses_per_graduate"],
                                        n_grad))
    k[~grads] = order.permutation(spread(prof["courses_per_undergraduate"],
                                         n - n_grad))
    every = int(prof["undergraduates_per_advisee"])
    advised = order.permutation(np.arange(n) % every == 0)
    return grads, k, advised


class UpdateSource:
    """Draws enrolments and withdrawals against the current population.

    An enrolment adds a new student, with the triples the generator
    makes for one, to a uniformly chosen department; a withdrawal
    removes a uniformly chosen current student with every triple about
    it.  The kinds, the graduate flags, the course counts and the
    advisor flags of enrolments come as fixed multisets in the order
    `order` draws (default `rng`), so every seed draws the same sizes."""

    def __init__(self, u: Universe, rng, n_ops: int, enrol_share: float,
                 order=None):
        self.u = u
        self.rng = rng
        order = rng if order is None else order
        self.pop = list(range(len(u.students)))   # current students
        self.added: dict[int, Student] = {}       # enrolled in the stream
        self.next_key = len(self.pop)
        n_enrol = int(round(n_ops * enrol_share))
        self.kinds = order.permutation(
            np.r_[np.ones(n_enrol, bool), np.zeros(n_ops - n_enrol, bool)])
        self.grads, self.ncrs, self.advised = _shapes(order, n_enrol, u)
        self.next_id = u.next_id
        self.n = 0
        self.n_enrol = 0

    def op(self) -> tuple[np.ndarray, np.ndarray]:
        """(inserts, deletes) of the next operation."""
        u, rng = self.u, self.rng
        kind = self.kinds[self.n]
        self.n += 1
        empty = np.zeros((0, 3), np.int32)
        if kind or not self.pop:
            if self.next_id + IDS_PER_ENROLMENT > u.id_limit:
                raise ValueError("enrolment pool exhausted")
            ids = range(self.next_id, self.next_id + IDS_PER_ENROLMENT)
            self.next_id += IDS_PER_ENROLMENT
            j = self.n_enrol % len(self.grads)
            self.n_enrol += 1
            st = _new_student(u, rng, ids, bool(self.grads[j]),
                              int(self.ncrs[j]), bool(self.advised[j]))
            self.added[self.next_key] = st
            self.pop.append(self.next_key)
            self.next_key += 1
            return student_triples(u, st), empty
        at = int(rng.integers(0, len(self.pop)))
        key = self.pop[at]
        self.pop[at] = self.pop[-1]
        self.pop.pop()
        if key in self.added:
            return empty, student_triples(u, self.added.pop(key))
        return empty, u.rows_of(int(u.students[key]))


def warmup_batches(u: Universe, rng, n: int) -> list[tuple[np.ndarray,
                                                           np.ndarray]]:
    """Two update batches that leave the store as it was: `n` students
    enrolled from the top of the id pool, then withdrawn.  Between them
    they drive the insert and the delete side of a maintenance pass."""
    empty = np.zeros((0, 3), np.int32)
    grads, k, advised = _shapes(rng, n, u)
    rows = []
    for j in range(n):
        top = u.id_limit - IDS_PER_ENROLMENT * (j + 1)
        st = _new_student(u, rng, range(top, top + IDS_PER_ENROLMENT),
                          bool(grads[j]), int(k[j]), bool(advised[j]))
        rows.append(student_triples(u, st))
    ins = np.concatenate(rows) if rows else empty
    return [(ins, empty), (empty, ins)]


def schema_ids(vocab: dict[str, int]) -> dict:
    """The ontology in ids: subclass and subproperty edges, domains and
    ranges."""
    return {"subclass": [(vocab[c], vocab[p]) for c, p in SUBCLASS],
            "subprop": [(vocab[c], vocab[p]) for c, p in SUBPROP],
            "domain": {vocab[p]: vocab[d] for p, (d, _r) in PROPS.items()
                       if d is not None},
            "range": {vocab[p]: vocab[r] for p, (_d, r) in PROPS.items()
                      if r is not None}}
