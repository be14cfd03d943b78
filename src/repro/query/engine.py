"""JAX query engine: static-capacity padded relations.

XLA requires static shapes, so every relation is a `(capacity, width)`
int32 buffer + a valid-row count + an overflow flag.  Capacities come
from the same cardinality estimates the quality function uses
(`cost.capacity_for`).  Invariants:

  * valid rows occupy a prefix `[0, n)`;
  * rows at `[n, capacity)` are scrubbed to -1 (no stale ids);
  * `overflow` latches if any operator's true output exceeded capacity.

Joins are sort + `searchsorted` + bounded expansion via
`jnp.repeat(..., total_repeat_length=cap)` — the TPU-native replacement
for dynamic hash tables.  The probe phase can be delegated to the Pallas
kernel (`kernels/ops.py`) with `use_pallas=True`.  A join whose right
side is a bound-predicate triple pattern can instead `probe` a sorted TT
index in place: binary searches per left key, no scan buffer, no sort.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import trace
from repro.core.queries import Const, Var
from repro.query import cost as cost_mod
from repro.query.plan import EquiJoin, Filter, Plan, Project, TTScan, ViewRef

INVALID = jnp.int32(-1)
SENTINEL_HI = jnp.int32(2**31 - 1)


class PRel(NamedTuple):
    data: jax.Array      # (cap, w) int32
    n: jax.Array         # () int32
    overflow: jax.Array  # () bool

    @property
    def cap(self) -> int:
        return self.data.shape[0]

    @property
    def width(self) -> int:
        return self.data.shape[1]


def make_prel(rows: np.ndarray, cap: int) -> PRel:
    rows = np.asarray(rows, dtype=np.int32)
    n = min(len(rows), cap)
    w = rows.shape[1] if rows.ndim == 2 else 0
    buf = np.full((cap, w), -1, dtype=np.int32)
    buf[:n] = rows[:n]
    return PRel(jnp.asarray(buf), jnp.int32(n), jnp.asarray(len(rows) > cap))


def to_numpy(rel: PRel) -> np.ndarray:
    with trace.span("rdfviews.query.to_numpy"):
        n = int(rel.n)
        return np.asarray(rel.data[:n])


def _valid_mask(rel: PRel) -> jax.Array:
    return jnp.arange(rel.cap, dtype=jnp.int32) < rel.n


def compact(data: jax.Array, mask: jax.Array, overflow: jax.Array) -> PRel:
    """Stable-partition valid rows to the front and scrub the tail."""
    perm = jnp.argsort(~mask)  # False (valid) sorts first; argsort is stable
    data = data[perm]
    n = jnp.sum(mask).astype(jnp.int32)
    keep = jnp.arange(data.shape[0], dtype=jnp.int32) < n
    data = jnp.where(keep[:, None], data, INVALID)
    return PRel(data, n, overflow)


# ----------------------------------------------------------------------
# operators
# ----------------------------------------------------------------------
def filter_eq(rel: PRel, col: int, value) -> PRel:
    # value may be a traced scalar (bucketed execution stacks the filter
    # constants of a whole bucket into one operand array)
    mask = _valid_mask(rel) & (rel.data[:, col] == jnp.asarray(value, jnp.int32))
    return compact(rel.data, mask, rel.overflow)


def _expand(left: PRel, lkeys: jax.Array, lo: jax.Array, counts: jax.Array,
            rrows: jax.Array, residual: tuple[tuple[int, int], ...],
            keep_right: tuple[int, ...], out_cap: int, overflow: jax.Array,
            self_eq: tuple[tuple[int, int], ...] = ()) -> PRel:
    """The output tail shared by `join` and `probe`: left row i matches
    rows `[lo[i], lo[i] + counts[i])` of `rrows`.  Expands the matches
    into `out_cap` rows (`repeat` with a static length), keeps those
    whose right row has equal columns at each `self_eq` pair and that
    pass the residual `(left col, right col)` pairs, outputs left's
    columns then right's `keep_right`, and latches `overflow` when the
    matches exceed `out_cap`."""
    counts = jnp.where(lkeys == INVALID, 0, counts)
    total = jnp.sum(counts)
    offsets = jnp.cumsum(counts) - counts  # exclusive prefix
    left_idx = jnp.repeat(
        jnp.arange(left.cap, dtype=jnp.int32), counts, total_repeat_length=out_cap
    )
    pos = jnp.arange(out_cap, dtype=jnp.int32)
    within = pos - offsets[left_idx]
    right_idx = jnp.clip(lo[left_idx] + within, 0, rrows.shape[0] - 1)
    valid = pos < jnp.minimum(total, out_cap)

    lrows = left.data[left_idx]
    rrows = rrows[right_idx]
    for a, b in self_eq:
        valid = valid & (rrows[:, a] == rrows[:, b])
    for lc, rc in residual:
        valid = valid & (lrows[:, lc] == rrows[:, rc])
    out = jnp.concatenate([lrows, rrows[:, list(keep_right)]], axis=1) if keep_right \
        else lrows
    return compact(out, valid, overflow | (total > out_cap))


def join(left: PRel, right: PRel, lcol: int, rcol: int,
         residual: tuple[tuple[int, int], ...], keep_right: tuple[int, ...],
         out_cap: int, use_pallas: bool = False,
         right_sorted: bool = False) -> PRel:
    """Equi-join on one column pair + residual equality pairs.

    Output columns: all of left's, then right's `keep_right`.
    `right_sorted=True` skips the build-side sort (the planner proved the
    input arrives ordered by `rcol` — six-index sort elision).
    """
    lvalid = _valid_mask(left)
    rvalid = _valid_mask(right)
    lkeys = jnp.where(lvalid, left.data[:, lcol], INVALID)
    rkeys = jnp.where(rvalid, right.data[:, rcol], SENTINEL_HI)
    if right_sorted:
        # valid rows are a sorted prefix; the scrubbed tail maps to +inf
        rsorted = right.data
        rkeys_sorted = rkeys
    else:
        order = jnp.argsort(rkeys)
        rsorted = right.data[order]
        rkeys_sorted = rkeys[order]

    if use_pallas:
        from repro.kernels import ops as kops

        lo, counts = kops.join_count(lkeys, rkeys_sorted)
    else:
        lo = jnp.searchsorted(rkeys_sorted, lkeys, side="left").astype(jnp.int32)
        hi = jnp.searchsorted(rkeys_sorted, lkeys, side="right").astype(jnp.int32)
        counts = hi - lo
    return _expand(left, lkeys, lo, counts, rsorted, residual, keep_right,
                   out_cap, left.overflow | right.overflow)


def _bisect(index_data: jax.Array, cols: tuple[int, ...], keys: tuple,
            lo: jax.Array, hi: jax.Array, side: str) -> jax.Array:
    """Fixed-iteration binary search in rows `[lo, hi)` of a sorted
    index, vectorised over the shape of `lo`: the first row whose
    `cols` compare lexicographically `>= keys` (`side="left"`) or
    `> keys` (`side="right"`).  Rows outside `[lo, hi)` are never
    read; `n.bit_length()` halvings cover any range of an (n, 3)
    index, so the loop count is static."""
    n = index_data.shape[0]
    keys = tuple(jnp.asarray(k, jnp.int32) for k in keys)

    def before(at):  # row `at` < keys ("left") or <= keys ("right")
        out = None
        # one element gather per column: a gather of whole rows would
        # make the TPU relayout the (n, 3) index first
        for c, k in reversed(tuple(zip(cols, keys))):
            v = index_data[at, c]
            if out is None:
                out = (v < k) if side == "left" else (v <= k)
            else:
                out = (v < k) | ((v == k) & out)
        return out

    def step(_, carry):
        lo, hi = carry
        mid = lo + (hi - lo) // 2
        go = before(jnp.clip(mid, 0, max(n - 1, 0)))
        live = lo < hi
        return (jnp.where(live & go, mid + 1, lo),
                jnp.where(live & ~go, mid, hi))

    lo, _ = lax.fori_loop(0, max(n.bit_length(), 1), step, (lo, hi))
    return lo


def probe(left: PRel, index_data: jax.Array,
          prefix: tuple[tuple[int, int], ...], rpos: int, lcol: int,
          residual: tuple[tuple[int, int], ...], keep: tuple[int, ...],
          self_eq: tuple[tuple[int, int], ...], out_cap: int) -> PRel:
    """Equi-join of `left` with a triple pattern read in place from one
    sorted TT index, with no scan buffer and no build-side sort.

    `prefix` binds the index's leading columns as in `scan_pattern`;
    the index orders the prefix's rows by triple position `rpos` next,
    so each left key's matches are one contiguous range, found by two
    binary searches inside the prefix's range.  `residual` pairs a left
    column with a triple position; `keep` lists the triple positions
    output after left's columns; `self_eq` pairs the positions of a
    variable the pattern repeats.  Rows, overflow and set semantics are those
    of `join(left, scan_pattern(...), ...)`; the matches are gathered
    straight from the index by the same tail (`_expand`)."""
    cols = tuple(c for c, _ in prefix)
    vals = tuple(v for _, v in prefix)
    n = jnp.int32(index_data.shape[0])
    lo0 = _bisect(index_data, cols, vals, jnp.int32(0), n, "left")
    hi0 = _bisect(index_data, cols, vals, lo0, n, "right")
    lkeys = jnp.where(_valid_mask(left), left.data[:, lcol], INVALID)
    first = jnp.full(lkeys.shape, lo0, jnp.int32)
    last = jnp.full(lkeys.shape, hi0, jnp.int32)
    lo = _bisect(index_data, (rpos,), (lkeys,), first, last, "left")
    hi = _bisect(index_data, (rpos,), (lkeys,), lo, last, "right")

    return _expand(left, lkeys, lo, hi - lo, index_data, residual, keep,
                   out_cap, left.overflow, self_eq)


def project(rel: PRel, cols: tuple[int, ...], dedupe: bool) -> PRel:
    data = rel.data[:, list(cols)]
    mask = _valid_mask(rel)
    if not dedupe:
        data = jnp.where(mask[:, None], data, INVALID)
        return PRel(data, rel.n, rel.overflow)
    # lexicographic sort: iterate stable argsort minor->major, invalid last
    order = jnp.arange(rel.cap, dtype=jnp.int32)
    for c in reversed(range(data.shape[1])):
        keys = jnp.where(mask[order], data[order, c], SENTINEL_HI)
        order = order[jnp.argsort(keys)]
    sorted_rows = data[order]
    sorted_valid = mask[order]
    prev = jnp.roll(sorted_rows, 1, axis=0)
    same = jnp.all(sorted_rows == prev, axis=1)
    same = same.at[0].set(False)
    keep = sorted_valid & ~same
    return compact(sorted_rows, keep, rel.overflow)


def scan_pattern(index_data: jax.Array, prefix: tuple[tuple[int, int], ...],
                 residual: tuple[tuple[int, int], ...],
                 takes: tuple[int, ...], self_eq: tuple[tuple[int, int], ...],
                 cap: int) -> PRel:
    """Range scan of one sorted TT index for a triple pattern.

    index_data: (N,3) sorted lexicographically; `prefix` gives up to two
    (col, value) bindings covered by the sort order — the matching rows
    are one contiguous range.  A 1-binding prefix uses binary search; a
    2-binding prefix uses a fused rank reduction (lexicographic compare,
    single fused pass — the int32-safe substitute for a 64-bit fused key).
    residual: (col, value) equality filters not covered by the prefix.
    takes: variable positions to output; self_eq: same-var positions.
    Prefix/residual values may be traced scalars (the bucketed executor
    stacks the constants of a whole shape bucket into operand arrays);
    the column positions and `cap` stay static.
    """
    n_tt = index_data.shape[0]
    if len(prefix) == 0:
        lo = jnp.int32(0)
        # padded TT buffers (capacity-class maintenance uploads, shards)
        # end in SENTINEL_HI rows, which sort last in every index order —
        # count real rows so padding doesn't inflate the overflow check
        hi = jnp.sum(index_data[:, 0] != SENTINEL_HI).astype(jnp.int32)
    elif len(prefix) == 1:
        col = index_data[:, prefix[0][0]]
        key = jnp.asarray(prefix[0][1], jnp.int32)
        lo = jnp.searchsorted(col, key, side="left").astype(jnp.int32)
        hi = jnp.searchsorted(col, key, side="right").astype(jnp.int32)
    else:
        (c1, k1), (c2, k2) = prefix
        col1 = index_data[:, c1]
        col2 = index_data[:, c2]
        k1 = jnp.asarray(k1, jnp.int32)
        k2 = jnp.asarray(k2, jnp.int32)
        lt = (col1 < k1) | ((col1 == k1) & (col2 < k2))
        le = (col1 < k1) | ((col1 == k1) & (col2 <= k2))
        lo = jnp.sum(lt).astype(jnp.int32)
        hi = jnp.sum(le).astype(jnp.int32)
    pos = lo + jnp.arange(cap, dtype=jnp.int32)
    valid = pos < hi
    rows = index_data[jnp.clip(pos, 0, max(n_tt - 1, 0))]
    # distributed TT shards are padded with SENTINEL_HI rows; exclude them
    valid = valid & (rows[:, 0] != SENTINEL_HI)
    for c, v in residual:
        valid = valid & (rows[:, c] == jnp.asarray(v, jnp.int32))
    for a, b in self_eq:
        valid = valid & (rows[:, a] == rows[:, b])
    out = rows[:, list(takes)] if takes else rows[:, :0]
    overflow = (hi - lo) > cap
    return compact(out, valid, overflow)


# ----------------------------------------------------------------------
# plan compiler
# ----------------------------------------------------------------------
# all six index orders, as triple positions (s=0, p=1, o=2)
INDEX_NAMES = ("spo", "pos", "osp", "pso", "ops", "sop")
_INDEX_ORDERS = {
    "spo": (0, 1, 2), "pos": (1, 2, 0), "osp": (2, 0, 1),
    "pso": (1, 0, 2), "ops": (2, 1, 0), "sop": (0, 2, 1),
}


def atom_scan_spec(atom, prefer_sorted: str | None = None):
    """Static scan parameters for a TTScan node: pick the index whose sort
    prefix covers the most bound positions (exact contiguous range); among
    ties, prefer the index whose NEXT sort column is the variable a
    downstream merge join wants pre-sorted (sort elision).

    Returns (idx_name, prefix, residual, takes, self_eq, sorted_by) where
    sorted_by is the output variable the rows are ordered by (or None).
    """
    bound = {i: t.id for i, t in enumerate(atom.terms()) if isinstance(t, Const)}
    var_at = {i: t.name for i, t in enumerate(atom.terms())
              if isinstance(t, Var)}

    def next_var(cols, plen):
        for c in cols[plen:]:
            if c in var_at:
                return var_at[c]
            return None  # a bound residual column interrupts sortedness
        return None

    best = None  # (coverage, prefer_hit, idx_name, prefix)
    for idx_name, cols in _INDEX_ORDERS.items():
        prefix = []
        for c in cols:
            if c in bound:
                prefix.append((c, bound[c]))
            else:
                break
        hit = 1 if (prefer_sorted is not None
                    and next_var(cols, len(prefix)) == prefer_sorted) else 0
        key = (len(prefix), hit)
        if best is None or key > best[0]:
            best = (key, idx_name, tuple(prefix))
    _, best_idx, best_prefix = best
    covered = {c for c, _ in best_prefix}
    residual = tuple((c, v) for c, v in bound.items() if c not in covered)
    sorted_by = None
    if not residual:  # residual filters don't reorder, but sortedness on
        # the next column only holds when the prefix is exactly covered
        sorted_by = next_var(_INDEX_ORDERS[best_idx], len(best_prefix))
    takes: list[int] = []
    first: dict[str, int] = {}
    self_eq: list[tuple[int, int]] = []
    for posn, t in enumerate(atom.terms()):
        if isinstance(t, Var):
            if t.name in first:
                self_eq.append((first[t.name], posn))
            else:
                first[t.name] = posn
                takes.append(posn)
    return best_idx, best_prefix, residual, tuple(takes), tuple(self_eq), sorted_by


def probe_scan_spec(atom, rpos: int):
    """The index a `probe` reads a bound-predicate pattern through when
    joining on triple position `rpos`: one whose leading columns are
    exactly the bound positions (the prefix) and whose next column is
    `rpos`, so each join key's rows are one contiguous range.  With the
    predicate bound such an index always exists (`pso`/`pos` for one
    bound position, `pso`/`spo` or `pos` for two).  Returns (idx_name,
    prefix), or None when the predicate is a variable."""
    if not isinstance(atom.p, Const):
        return None
    bound = {i: t.id for i, t in enumerate(atom.terms()) if isinstance(t, Const)}
    for idx_name, cols in _INDEX_ORDERS.items():
        if set(cols[:len(bound)]) == set(bound) and cols[len(bound)] == rpos:
            return idx_name, tuple((c, bound[c]) for c in cols[:len(bound)])
    return None


def range_cardinality(atom, prefix, stats) -> float:
    """Estimated size of the contiguous index range (prefix-bound only) —
    this, not the fully-filtered estimate, sizes the scan buffer."""
    covered = {c for c, _ in prefix}
    p = atom.p.id if (1 in covered and isinstance(atom.p, Const)) else None
    o_val = atom.o.id if (2 in covered and isinstance(atom.o, Const)) else None
    return stats.atom_card(s_bound=0 in covered, p=p, o_bound=2 in covered,
                           o_val=o_val)


def build_executor(plan: Plan, stats, view_infos: dict[int, "cost_mod.RelInfo"],
                   safety: float = 4.0, use_pallas: bool = False,
                   cap_override: Callable[[Plan, float], int] | None = None):
    """Compile a plan into `fn(tt_indexes, views) -> PRel`.

    `tt_indexes`: {"spo"|"pos"|"osp": (N,3) int32 device array}
    `views`: {view_id: PRel}
    `view_infos`: {view_id: cost.RelInfo} — extent cardinality + per-column
    distincts (estimated from the view CQ, or measured after
    materialization).  Buffer capacities are static, sized from the same
    estimates the quality function uses; join lead columns are chosen to
    minimize pre-residual expansion.
    """

    def cap_of(node: Plan, rows: float) -> int:
        if cap_override is not None:
            return cap_override(node, rows)
        return cost_mod.capacity_for(rows, safety=safety)

    def build(node: Plan, prefer_sorted: str | None = None
              ) -> tuple[Callable, tuple[str, ...], "cost_mod.RelInfo", str | None]:
        """returns (fn, cols, info, sorted_by)"""
        est = cost_mod.estimate_plan(node, stats, view_infos)
        if isinstance(node, TTScan):
            idx_name, prefix, residual, takes, self_eq, sorted_by = \
                atom_scan_spec(node.atom, prefer_sorted)
            cap = cap_of(node, range_cardinality(node.atom, prefix, stats))
            cols = node.columns()

            def run(tt, views, _f=functools.partial(
                    scan_pattern, prefix=prefix, residual=residual,
                    takes=takes, self_eq=self_eq, cap=cap), _idx=idx_name):
                return _f(tt[_idx])

            return run, cols, est.info, sorted_by
        if isinstance(node, ViewRef):
            def run(tt, views, _vid=node.view_id):
                return views[_vid]

            return run, node.schema, est.info, None
        if isinstance(node, Filter):
            child_fn, cols, _, sorted_by = build(node.child, prefer_sorted)
            ci = cols.index(node.col)

            def run(tt, views, _fn=child_fn, _ci=ci, _v=node.value):
                return filter_eq(_fn(tt, views), _ci, _v)

            # compact() is stable: filtering preserves row order
            return run, cols, est.info, sorted_by
        if isinstance(node, EquiJoin):
            if not node.pairs:
                raise NotImplementedError(
                    "cartesian products are not compiled to the device engine; "
                    "disconnected rewritings stay on the oracle path"
                )
            # pick the lead pair from static estimates, then build children
            # with the sort preference so scans can elide the join sort
            l_est = cost_mod.estimate_plan(node.left, stats, view_infos)
            r_est = cost_mod.estimate_plan(node.right, stats, view_infos)
            doms = [
                max(l_est.info.dcol(l), r_est.info.dcol(r))
                for l, r in node.pairs
            ]
            lead_k = max(range(len(doms)), key=lambda i: doms[i])
            lead_pair = node.pairs[lead_k]
            lf, lcols, linfo, _ = build(node.left)
            rf, rcols, rinfo, r_sorted_by = build(node.right, lead_pair[1])
            lead = (lcols.index(lead_pair[0]), rcols.index(lead_pair[1]))
            residual = tuple(
                (lcols.index(l), rcols.index(r))
                for k, (l, r) in enumerate(node.pairs) if k != lead_k
            )
            lead_rows = max(linfo.rows * rinfo.rows / doms[lead_k], 1e-3)
            drop = {r for _, r in node.pairs}
            keep_right = tuple(i for i, c in enumerate(rcols) if c not in drop)
            out_cols = lcols + tuple(c for c in rcols if c not in drop)
            cap = cap_of(node, lead_rows)
            r_presorted = r_sorted_by == lead_pair[1]

            def run(tt, views, _lf=lf, _rf=rf, _lead=lead, _res=residual,
                    _keep=keep_right, _cap=cap, _rs=r_presorted):
                return join(_lf(tt, views), _rf(tt, views), _lead[0], _lead[1],
                            _res, _keep, _cap, use_pallas=use_pallas,
                            right_sorted=_rs)

            # join output follows left row-major order: sorted by nothing
            # we track (expansion interleaves groups)
            return run, out_cols, est.info, None
        if isinstance(node, Project):
            child_fn, cols, _, sorted_by = build(node.child, prefer_sorted)
            idx = tuple(cols.index(c) for c in node.cols)
            out_sorted = sorted_by if (not node.dedupe and sorted_by in node.cols) \
                else (node.cols[0] if node.dedupe else None)

            def run(tt, views, _fn=child_fn, _idx=idx, _d=node.dedupe):
                return project(_fn(tt, views), _idx, _d)

            return run, node.cols, est.info, out_sorted
        raise TypeError(type(node))

    fn, cols, info, _ = build(plan)
    fn.out_columns = cols   # type: ignore[attr-defined]
    fn.est_rows = info.rows  # type: ignore[attr-defined]
    return fn


def tt_device_indexes(store) -> dict[str, jax.Array]:
    return {name: jnp.asarray(store.index(name)) for name in INDEX_NAMES}


def tt_device_indexes_padded(store, cap: int) -> dict[str, jax.Array]:
    """TT indexes padded with SENTINEL_HI rows to a fixed capacity class.

    Streaming maintenance re-uploads TT' every batch; padding to a class
    keeps every scan operand shape constant while the store grows, so
    appends never recompile the workload program.  Sentinel rows sort
    after every real id in all six orders, preserving binary-search
    semantics, and `scan_pattern` masks them out."""
    if cap < len(store):
        raise ValueError(
            f"tt capacity class {cap} < store size {len(store)}")
    out = {}
    for name in INDEX_NAMES:
        data = store.index(name)
        buf = np.full((cap, 3), np.iinfo(np.int32).max, dtype=np.int32)
        buf[: len(data)] = data
        out[name] = jnp.asarray(buf)
    return out
