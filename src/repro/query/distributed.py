"""Distributed query evaluation: sharded TT + views, repartition joins.

The paper evaluated rewritings inside a single PostgreSQL node; at pod
scale the triple table and every materialized view are row-sharded by
hash over the `data` mesh axis.  A rewriting becomes one SPMD program
(`query_step`) built from:

  * local scans/filters (selections are row-local),
  * hash-repartition equi-joins: both sides are bucketed by
    `key % ndev` into fixed-capacity per-destination buckets and
    exchanged with `lax.all_to_all`, then joined locally — the classic
    distributed hash join on jax.lax collectives,
  * co-partition elision: when both inputs are already partitioned by
    the join column (tracked statically through the plan), the
    all_to_all is skipped — the main collective optimization knob.

Per-device buffers are the global estimates divided by the device count
times a skew factor, read from the `REPRO_QUERY_SKEW` environment
variable (default 4.0; ROADMAP design debt 4).

Buckets make the exchange static-shaped; overflow latches like the local
engine.  The final relation stays sharded; `gather_result` collects it.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.query import cost as cost_mod
from repro.query import engine as E
from repro.query.engine import INVALID, PRel, compact
from repro.query.plan import EquiJoin, Filter, Plan, Project, TTScan, ViewRef


# ----------------------------------------------------------------------
# repartition
# ----------------------------------------------------------------------
def bucket_by_dest(rel: PRel, key_col: int, ndev: int, bucket_cap: int) -> tuple[jax.Array, jax.Array]:
    """Pack rows into an (ndev, bucket_cap, w) send buffer by key % ndev.

    Returns (buffer, overflow).  Empty slots are -1."""
    w = rel.width
    valid = jnp.arange(rel.cap, dtype=jnp.int32) < rel.n
    dest = jnp.where(valid, rel.data[:, key_col] % ndev, ndev)
    order = jnp.argsort(dest)  # stable; invalid rows sort last
    sorted_dest = dest[order]
    sorted_rows = rel.data[order]
    # rank of each row within its destination group
    group_start = jnp.searchsorted(sorted_dest, sorted_dest, side="left")
    rank = jnp.arange(rel.cap, dtype=jnp.int32) - group_start.astype(jnp.int32)
    ok = (sorted_dest < ndev) & (rank < bucket_cap)
    slot = jnp.where(ok, sorted_dest * bucket_cap + rank, ndev * bucket_cap)
    buf = jnp.full((ndev * bucket_cap + 1, w), -1, dtype=jnp.int32)
    buf = buf.at[slot].set(sorted_rows)
    overflow = rel.overflow | jnp.any((sorted_dest < ndev) & (rank >= bucket_cap))
    return buf[:-1].reshape(ndev, bucket_cap, w), overflow


def repartition(rel: PRel, key_col: int, axis, ndev: int,
                bucket_cap: int) -> PRel:
    """Exchange rows so that equal keys land on the same device.

    `axis` may be one mesh axis name or a tuple (the partition space is
    the flattened product, e.g. ("data","model") = the whole pod)."""
    buf, overflow = bucket_by_dest(rel, key_col, ndev, bucket_cap)
    recv = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0)
    data = recv.reshape(ndev * bucket_cap, rel.width)
    mask = data[:, 0] != INVALID
    out = compact(data, mask, overflow)
    # overflow is device-local; make the flag global so every shard agrees
    return PRel(out.data, out.n, jax.lax.pmax(out.overflow.astype(jnp.int32), axis) > 0)


# ----------------------------------------------------------------------
# distributed plan compiler
# ----------------------------------------------------------------------
def build_distributed_executor(plan: Plan, stats, view_infos, mesh,
                               axis="data", safety: float = 4.0,
                               partition_cols: dict[int, str] | None = None,
                               final_gather: bool = False):
    """Compile `plan` into an SPMD function over `mesh` axis `axis`.

    `partition_cols` maps view_id -> column name the extent is hash-
    partitioned by (enables co-partition elision; the TT is partitioned
    by subject).  Per-device capacities are the global estimates divided
    by ndev times a skew factor.

    Returns `fn(tt_shards, view_shards) -> PRel` wrapped in shard_map;
    inputs are globally-sharded arrays, output is the sharded result.
    """
    import os

    ndev = int(np.prod([mesh.shape[a] for a in (axis if isinstance(axis, tuple) else (axis,))]))
    partition_cols = partition_cols or {}
    SKEW = float(os.environ.get("REPRO_QUERY_SKEW", "4.0"))

    def cap_of(rows_global: float) -> int:
        per_dev = rows_global / ndev * SKEW
        return cost_mod.capacity_for(per_dev, safety=safety)

    def build(node: Plan, prefer_sorted: str | None = None
              ) -> tuple[Callable, tuple[str, ...], object, str | None, str | None]:
        """returns (fn, cols, info, partitioned_by|None, sorted_by|None)"""
        est = cost_mod.estimate_plan(node, stats, view_infos)
        if isinstance(node, TTScan):
            idx_name, prefix, residual, takes, self_eq, sorted_by = \
                E.atom_scan_spec(node.atom, prefer_sorted)
            cap = cap_of(E.range_cardinality(node.atom, prefix, stats))
            cols = node.columns()
            # the TT is hash(s)-partitioned: a scan output inherits the
            # subject partitioning iff it keeps the subject column
            from repro.core.queries import Var
            part = node.atom.s.name if isinstance(node.atom.s, Var) else None

            def run(tt, views, _f=functools.partial(
                    E.scan_pattern, prefix=prefix, residual=residual,
                    takes=takes, self_eq=self_eq, cap=cap), _idx=idx_name):
                return _f(tt[_idx])

            return run, cols, est.info, part, sorted_by
        if isinstance(node, ViewRef):
            part_src = partition_cols.get(node.view_id)
            # positional alignment: view head name -> plan-local name
            part = None
            if part_src is not None and part_src in node.schema:
                part = part_src

            def run(tt, views, _vid=node.view_id):
                return views[_vid]

            return run, node.schema, est.info, part, None
        if isinstance(node, Filter):
            child_fn, cols, _, part, sorted_by = build(node.child, prefer_sorted)
            ci = cols.index(node.col)

            def run(tt, views, _fn=child_fn, _ci=ci, _v=node.value):
                return E.filter_eq(_fn(tt, views), _ci, _v)

            return run, cols, est.info, part, sorted_by
        if isinstance(node, EquiJoin):
            if not node.pairs:
                raise NotImplementedError("cartesian products not supported distributed")
            lead_k, lead_rows = cost_mod.join_lead(
                node.pairs,
                cost_mod.estimate_plan(node.left, stats, view_infos).info,
                cost_mod.estimate_plan(node.right, stats, view_infos).info)
            lead_pair = node.pairs[lead_k]
            lf, lcols, linfo, lpart, _ = build(node.left)
            rf, rcols, rinfo, rpart, r_sorted_by = build(node.right,
                                                         lead_pair[1])
            li, ri = lcols.index(lead_pair[0]), rcols.index(lead_pair[1])
            residual = tuple(
                (lcols.index(l), rcols.index(r))
                for k, (l, r) in enumerate(node.pairs) if k != lead_k
            )
            drop = {r for _, r in node.pairs}
            keep_right = tuple(i for i, c in enumerate(rcols) if c not in drop)
            out_cols = lcols + tuple(c for c in rcols if c not in drop)
            out_cap = cap_of(lead_rows)
            # per-destination bucket: rows/(ndev^2) with skew headroom
            lbucket = cost_mod.capacity_for(
                linfo.rows / (ndev * ndev) * SKEW * 2, safety=safety, floor=16)
            rbucket = cost_mod.capacity_for(
                rinfo.rows / (ndev * ndev) * SKEW * 2, safety=safety, floor=16)
            l_colocated = lpart == lead_pair[0] and lpart is not None
            r_colocated = rpart == lead_pair[1] and rpart is not None
            # sort elision survives only when the right side is NOT
            # repartitioned (the exchange destroys row order)
            r_presorted = r_colocated and r_sorted_by == lead_pair[1]

            def run(tt, views, _lf=lf, _rf=rf, _li=li, _ri=ri, _res=residual,
                    _keep=keep_right, _cap=out_cap, _lb=lbucket, _rb=rbucket,
                    _lcol=l_colocated, _rcol=r_colocated, _rs=r_presorted):
                left = _lf(tt, views)
                right = _rf(tt, views)
                # co-partition elision: only repartition sides not already
                # hashed on the lead join column
                if not (_lcol and _rcol):
                    if not _lcol:
                        left = repartition(left, _li, axis, ndev, _lb)
                    if not _rcol:
                        right = repartition(right, _ri, axis, ndev, _rb)
                return E.join(left, right, _li, _ri, _res, _keep, _cap,
                              right_sorted=_rs)

            return run, out_cols, est.info, lead_pair[0], None
        if isinstance(node, Project):
            child_fn, cols, _, part, sorted_by = build(node.child, prefer_sorted)
            idx = tuple(cols.index(c) for c in node.cols)
            out_part = part if part in node.cols else None
            out_sorted = sorted_by if (not node.dedupe and sorted_by in node.cols) \
                else (node.cols[0] if node.dedupe else None)

            def run(tt, views, _fn=child_fn, _idx=idx, _d=node.dedupe):
                rel = _fn(tt, views)
                # local dedupe is enough: rows are co-partitioned by the
                # kept partition column or will be deduped at gather
                return E.project(rel, _idx, _d)

            return run, node.cols, est.info, out_part, out_sorted
        raise TypeError(type(node))

    fn, cols, info, part, _sorted = build(plan)

    in_specs = ({k: P(axis) for k in E.INDEX_NAMES},
                {vid: PRel(P(axis), P(axis), P()) for vid in view_infos})
    out_specs = PRel(P(axis), P(axis), P(axis))

    def local_program(tt, views):
        # unwrap per-shard views: n arrives as a (1,) slice of the global
        # per-device count vector
        views = {vid: PRel(v.data, v.n.reshape(()), v.overflow)
                 for vid, v in views.items()}
        out = fn(tt, views)
        return PRel(out.data, out.n.reshape(1), out.overflow.reshape(1))

    smapped = jax.shard_map(local_program, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    smapped.out_columns = cols  # type: ignore[attr-defined]
    smapped.est_rows = info.rows  # type: ignore[attr-defined]
    return smapped


# ----------------------------------------------------------------------
# host helpers
# ----------------------------------------------------------------------
def shard_store_by_subject(store, mesh, axis: str = "data",
                           with_shards: bool = False):
    """Partition the TT by hash(subject); per-shard local sorted indexes,
    stacked into global arrays sharded over `axis`.

    Empty shards are legal (hash skew, or ndev > distinct subjects —
    common on tiny stores over wide meshes): they stack as all-sentinel
    slabs, which every index order sorts last and `scan_pattern` masks,
    so downstream searchsorted sees a valid zero-row sorted index.  The
    per-shard capacity always covers the longest shard even past the
    planner's power-of-two ceiling, so a heavily skewed shard can never
    truncate rows.  `with_shards=True` additionally returns the host-
    side per-shard `TripleStore`s (the mirrors a sharded serving backend
    probes against and falls back to when a device shard degrades).
    """
    ndev = mesh.shape[axis]
    t = store.triples
    dest = t[:, 0] % ndev
    from repro.rdf.triples import TripleStore

    shards = [TripleStore(t[dest == d]) for d in range(ndev)]
    longest = max((len(s) for s in shards), default=0)
    cap = max(cost_mod.capacity_for(max(longest, 1), safety=1.0),
              max(longest, 1))

    out: dict[str, np.ndarray] = {}
    for name in E.INDEX_NAMES:
        stacked = np.full((ndev, cap, 3), 2**31 - 1, dtype=np.int32)
        for d, s in enumerate(shards):
            idx = s.index(name)
            stacked[d, : len(idx)] = idx
        out[name] = stacked.reshape(ndev * cap, 3)
    sharding = NamedSharding(mesh, P(axis))
    tt = {k: jax.device_put(v, sharding) for k, v in out.items()}
    return (tt, shards) if with_shards else tt


def shard_prel_rows(rows: np.ndarray, key_col: int, mesh, axis: str = "data",
                    cap_per_dev: int | None = None,
                    width: int | None = None) -> PRel:
    """Hash-partition extent rows by `key_col` into a sharded PRel.

    A zero-row extent is valid input, including the degenerate 1-D empty
    array numpy produces for `[]` — it is normalized to a (0, width)
    table (`width` defaults to `key_col + 1`) so every shard gets an
    empty-but-well-shaped slab instead of crashing on the column index.
    """
    ndev = mesh.shape[axis]
    rows = np.asarray(rows, np.int32)
    if rows.ndim != 2:
        rows = rows.reshape(0, width if width else key_col + 1)
    dest = rows[:, key_col] % ndev
    groups = [rows[dest == d] for d in range(ndev)]
    cap = cap_per_dev or cost_mod.capacity_for(
        max(max((len(g) for g in groups), default=1), 1), safety=2.0)
    data = np.full((ndev, cap, rows.shape[1]), -1, dtype=np.int32)
    ns = np.zeros((ndev,), np.int32)
    for d, g in enumerate(groups):
        k = min(len(g), cap)
        data[d, :k] = g[:k]
        ns[d] = k
    sh_rows = NamedSharding(mesh, P(axis))
    return PRel(
        jax.device_put(data.reshape(ndev * cap, rows.shape[1]), sh_rows),
        jax.device_put(ns, sh_rows),
        jax.device_put(np.asarray(False), NamedSharding(mesh, P())),
    )


def gather_result(rel: PRel) -> np.ndarray:
    """Collect a sharded result to the host (set semantics: dedupe rows
    that a head projection may have duplicated across shards)."""
    data = np.asarray(rel.data)
    mask = data[:, 0] != -1 if data.shape[1] else np.zeros(len(data), bool)
    rows = data[mask]
    return np.unique(rows, axis=0) if len(rows) else rows
