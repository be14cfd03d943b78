"""Shape-bucketed lowering of a `WorkloadDAG`: one scanned body per bucket.

This is the single-chip lowering of the plan IR: the serving program,
the maintainer's delta program, device materialization and the serving
ladder's per-query fallback all run through it.  Tracing one closure per
DAG node would make XLA graph size — and compile time — grow linearly
with the workload; this module instead applies the scan-over-layers
idiom (levanter's `Stacked`, SNIPPETS.md Snippet 1) to query plans:
nodes are grouped into *shape buckets* by

    (topological wave, operator kind, structural signature, capacity class)

and each bucket executes as ONE `lax.scan` over its members' stacked
operands.  The per-element constants (scan prefix/residual bindings,
filter values) become data; the structure (column positions, join pairs,
buffer capacities) stays static, so XLA traces and compiles each bucket
body exactly once regardless of how many workload members share it.
Compile time therefore scales with the number of *distinct shapes* in
the workload, not with the number of queries.

Bucket bodies are compiled ahead-of-time (`jax.jit(...).lower().compile()`)
through a process-global `CompileCache` keyed by (kind, static spec,
operand shapes).  The cache persists across program rebuilds — a
`TuningSession.retune()+apply()` hot swap whose new DAG reuses old shapes
pays zero cold compiles on the serving path — and it gives the adaptive
overflow driver bucket-scoped recompiles: promoting one bucket to the
next capacity class invalidates only that bucket's body (plus any
consumer whose operand shape actually changed); every other body is a
cache hit.

Capacity classes are powers of two (`cost.capacity_for` /
`cost.promote_capacity`).  Consumers pad child buffers up to their
bucket's per-slot maximum capacity (padded rows are `-1`-scrubbed and
sit beyond the valid count, so operators never see them), which keeps a
bucket batchable even after one producer bucket has been promoted past
its siblings.

A join whose right child is a bound-predicate TT scan lowers to a
`probe` bucket instead when its left side is small: the members' left
keys are binary-searched in the sorted TT index itself (`engine.probe`),
so neither the scan buffer nor the build-side sort exists.  The choice
is static — probe when `left_cap * ceil(log2 tt_rows) < right scan
cap` — and a scan every consumer of which probes is dropped.
"""
from __future__ import annotations

import time
from collections import Counter, OrderedDict
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import trace
from repro.query import cost as cost_mod
from repro.query import engine as E
from repro.query.cost import CAP_CEIL
from repro.query.dag import WorkloadDAG

# the program span of one bucket dispatch, by operator kind
_BUCKET_SPANS = {k: f"rdfviews.query.bucket.{k}"
                 for k in ("scan", "filter", "join", "probe", "project")}

# Default LRU bound of the process-global compile cache.  A long-lived
# TuningSession.retune() loop churns through bucket shapes; without a
# bound every shape ever compiled stays resident (XLA executables hold
# device memory) for the life of the process.
DEFAULT_CACHE_ENTRIES = 512


# ----------------------------------------------------------------------
# persistent compile cache
# ----------------------------------------------------------------------
class CompileCache:
    """Process-global LRU cache of AOT-compiled bucket bodies.

    Keyed by (kind, static signature, operand shape/dtype tuple): the
    key pins everything that affects the traced program, so an entry is
    valid for any executor in the process — rebuilt programs after a
    view hot swap reuse every body whose shape survived.

    Bounded to `max_entries` (LRU eviction): long-lived retune() loops
    keep only their working set of shapes resident instead of every
    shape ever compiled.  Evictions surface in `stats()` and through
    executor telemetry; an evicted body is simply a future cache miss.
    """

    def __init__(self, max_entries: int = DEFAULT_CACHE_ENTRIES) -> None:
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = max_entries
        self.entries: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compile_seconds = 0.0

    def get(self, key, build_fn, arg_specs):
        """Return (compiled, cached, seconds): `compiled` is an AOT
        executable accepting concrete arrays of `arg_specs` shapes."""
        ent = self.entries.get(key)
        if ent is not None:
            self.hits += 1
            self.entries.move_to_end(key)  # most-recently used
            return ent, True, 0.0
        t0 = time.perf_counter()
        compiled = jax.jit(build_fn()).lower(*arg_specs).compile()
        dt = time.perf_counter() - t0
        self.entries[key] = compiled
        self.misses += 1
        self.compile_seconds += dt
        self._evict()
        return compiled, False, dt

    def _evict(self) -> None:
        while len(self.entries) > self.max_entries:
            self.entries.popitem(last=False)  # least-recently used
            self.evictions += 1

    def resize(self, max_entries: int) -> None:
        """Change the LRU bound in place (evicting immediately if the
        cache already exceeds the new bound)."""
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = max_entries
        self._evict()

    def clear(self) -> None:
        self.entries.clear()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compile_seconds = 0.0

    def stats(self) -> dict:
        return {"entries": len(self.entries),
                "max_entries": self.max_entries,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "compile_seconds": self.compile_seconds}


_CACHE = CompileCache()


def compile_cache() -> CompileCache:
    return _CACHE


def clear_compile_cache() -> None:
    """Drop every cached bucket body (benchmarks measuring cold-compile
    scaling call this between sweep points)."""
    _CACHE.clear()


# ----------------------------------------------------------------------
# bucket planning
# ----------------------------------------------------------------------
@dataclass
class Bucket:
    """One shape bucket: members share kind, structural signature and
    capacity class, and sit on the same topological wave (so no member
    depends on another — the batch is embarrassingly parallel and safe
    to drive with one `lax.scan`)."""

    kind: str
    wave: int
    static: tuple                 # structural signature (positions only)
    cap: int                      # output capacity class (0: filter/project)
    node_ids: list[int] = field(default_factory=list)
    promotions: int = 0
    # scan buckets: per-member pattern constants (prefix, residual);
    # probe buckets: prefix only; stacked + uploaded at build time
    pvals: jax.Array | None = None
    rvals: jax.Array | None = None

    @property
    def label(self) -> str:
        return f"w{self.wave}:{self.kind}:cap{self.cap}:n{len(self.node_ids)}"


def node_waves(dag: WorkloadDAG) -> list[int]:
    """Topological wave per node: leaves at 0, inner nodes one past
    their deepest child.  Children always sit on strictly lower waves,
    so same-wave nodes can never depend on each other."""
    waves: list[int] = []
    for node in dag.nodes:
        if node.child_ids:
            waves.append(1 + max(waves[c] for c in node.child_ids))
        else:
            waves.append(0)
    return waves


def plan_probes(dag: WorkloadDAG, caps: list[int], scan_specs: dict,
                join_specs: dict, eff_caps: list[int],
                tt_rows: int) -> dict[int, tuple]:
    """The joins that lower to index probes, from static capacities.

    A join qualifies when its right child is a TT scan of a bound
    predicate that some index orders by the join column right after a
    bound prefix (`engine.probe_scan_spec`), and probing is the cheaper
    side: `left_cap * ceil(log2 tt_rows) < right scan cap` (binary
    searches per left row against scanning and sorting the range).
    Returns {join nid: (idx_name, prefix, rpos, lcol, residual, keep,
    self_eq)}, every column in it a triple position except `lcol` and
    the left halves of `residual`."""
    log_n = max((int(tt_rows) - 1).bit_length(), 1)
    out: dict[int, tuple] = {}
    for node in dag.nodes:
        if node.kind != "join":
            continue
        lid, rid = node.child_ids
        if dag.nodes[rid].kind != "scan" or eff_caps[lid] * log_n >= caps[rid]:
            continue
        lcol, rcol, residual, keep_right = join_specs[node.id]
        _, _, _, takes, self_eq = scan_specs[rid]
        spec = E.probe_scan_spec(dag.nodes[rid].spec, takes[rcol])
        if spec is None:
            continue
        idx_name, prefix = spec
        out[node.id] = (idx_name, prefix, takes[rcol], lcol,
                        tuple((l, takes[r]) for l, r in residual),
                        tuple(takes[i] for i in keep_right), self_eq)
    return out


def plan_buckets(dag: WorkloadDAG, caps: list[int], scan_specs: dict,
                 join_specs: dict, probes: dict | None = None
                 ) -> tuple[list[Bucket], dict[int, Bucket]]:
    """Group every non-view node into shape buckets.

    `caps` holds the planned output capacity class per node (scan/join;
    unused entries 0).  `scan_specs[nid]` / `join_specs[nid]` hold the
    static lowering parameters produced by `BucketedProgram`, and
    `probes` the joins lowered to index probes (`plan_probes`); a scan
    whose every consumer probes is not run at all.  Returns (buckets in
    execution order, node id -> bucket).
    """
    probes = probes or {}
    probed = Counter(dag.nodes[nid].child_ids[1] for nid in probes)
    waves = node_waves(dag)
    by_key: dict[tuple, Bucket] = {}
    node_bucket: dict[int, Bucket] = {}
    for node in dag.nodes:
        kind = node.kind
        if kind == "view" or (kind == "scan" and probed[node.id]
                              == dag.consumers.get(node.id, 0)):
            continue
        if node.id in probes:
            kind = "probe"
            idx_name, prefix, rpos, lcol, residual, keep, self_eq = \
                probes[node.id]
            lw = dag.nodes[node.child_ids[0]].width
            static = ("probe", idx_name, tuple(c for c, _ in prefix), rpos,
                      lcol, residual, keep, self_eq, lw)
            cap = caps[node.id]
        elif node.kind == "scan":
            idx_name, prefix, residual, takes, self_eq = scan_specs[node.id]
            static = ("scan", idx_name, tuple(c for c, _ in prefix),
                      tuple(c for c, _ in residual), takes, self_eq)
            cap = caps[node.id]
        elif node.kind == "filter":
            ci, _value = node.spec
            static = ("filter", ci, node.width)
            cap = 0
        elif node.kind == "join":
            lcol, rcol, residual, keep_right = join_specs[node.id]
            lw = dag.nodes[node.child_ids[0]].width
            rw = dag.nodes[node.child_ids[1]].width
            static = ("join", lcol, rcol, residual, keep_right, lw, rw)
            cap = caps[node.id]
        elif node.kind == "project":
            idxs, dedupe = node.spec
            cw = dag.nodes[node.child_ids[0]].width
            static = ("project", idxs, dedupe, cw)
            cap = 0
        else:
            raise TypeError(node.kind)
        key = (waves[node.id], static, cap)
        bucket = by_key.get(key)
        if bucket is None:
            bucket = Bucket(kind=kind, wave=waves[node.id],
                            static=static, cap=cap)
            by_key[key] = bucket
        bucket.node_ids.append(node.id)
        node_bucket[node.id] = bucket
    order = sorted(by_key.values(),
                   key=lambda b: (b.wave, min(b.node_ids)))
    return order, node_bucket


# ----------------------------------------------------------------------
# bucket bodies (built from the cache key alone — pure shape functions)
# ----------------------------------------------------------------------
def _scan_body(static, cap):
    _, _idx_name, prefix_cols, residual_cols, takes, self_eq = static

    def fn(index_data, pvals, rvals):
        def step(carry, xs):
            pv, rv = xs
            prefix = tuple((c, pv[i]) for i, c in enumerate(prefix_cols))
            residual = tuple((c, rv[i]) for i, c in enumerate(residual_cols))
            return carry, E.scan_pattern(index_data, prefix, residual,
                                         takes, self_eq, cap)

        _, out = lax.scan(step, None, (pvals, rvals))
        return out

    return fn


def _filter_body(static):
    _, ci, _width = static

    def fn(cdata, cn, covf, vals):
        def step(carry, xs):
            d, n, o, v = xs
            return carry, E.filter_eq(E.PRel(d, n, o), ci, v)

        _, out = lax.scan(step, None, (cdata, cn, covf, vals))
        return out

    return fn


def _join_body(static, cap, use_pallas):
    _, lcol, rcol, residual, keep_right, _lw, _rw = static

    def fn(ldata, ln, lovf, rdata, rn, rovf):
        def step(carry, xs):
            ld, ln_, lo, rd, rn_, ro = xs
            return carry, E.join(E.PRel(ld, ln_, lo), E.PRel(rd, rn_, ro),
                                 lcol, rcol, residual, keep_right, cap,
                                 use_pallas=use_pallas)

        _, out = lax.scan(step, None, (ldata, ln, lovf, rdata, rn, rovf))
        return out

    return fn


def _probe_body(static, cap):
    _, _idx_name, prefix_cols, rpos, lcol, residual, keep, self_eq, _lw = \
        static

    def fn(ldata, ln, lovf, index_data, pvals):
        def step(carry, xs):
            ld, ln_, lo, pv = xs
            prefix = tuple((c, pv[i]) for i, c in enumerate(prefix_cols))
            return carry, E.probe(E.PRel(ld, ln_, lo), index_data, prefix,
                                  rpos, lcol, residual, keep, self_eq, cap)

        _, out = lax.scan(step, None, (ldata, ln, lovf, pvals))
        return out

    return fn


def _project_body(static):
    _, idxs, dedupe, _cw = static

    def fn(cdata, cn, covf):
        def step(carry, xs):
            d, n, o = xs
            return carry, E.project(E.PRel(d, n, o), idxs, dedupe)

        _, out = lax.scan(step, None, (cdata, cn, covf))
        return out

    return fn


def body_builder(bucket: Bucket, use_pallas: bool = False,
                 role: str = "workload"):
    """The traced body function for one bucket, built from its static
    signature alone — the same builder `_run_bucket` compiles through
    the cache, exposed so the jaxpr lint (`repro.analysis.jaxpr_lint`)
    can trace every body abstractly without executing anything.

    The body is named `<role>_<kind>` (`role` is the program's: see
    `BucketedProgram`), so a device trace shows it as
    `jit_<role>_<kind>`, and its operator runs under
    `jax.named_scope(<kind>)`."""
    kind = bucket.kind
    if kind == "scan":
        op = _scan_body(bucket.static, bucket.cap)
    elif kind == "filter":
        op = _filter_body(bucket.static)
    elif kind == "join":
        op = _join_body(bucket.static, bucket.cap, use_pallas)
    elif kind == "probe":
        op = _probe_body(bucket.static, bucket.cap)
    elif kind == "project":
        op = _project_body(bucket.static)
    else:
        raise TypeError(kind)

    def fn(*args):
        with jax.named_scope(kind):
            return op(*args)

    fn.__name__ = fn.__qualname__ = f"{role}_{kind}"
    return fn


def _specs_of(args) -> tuple:
    return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)


def _shape_key(specs) -> tuple:
    return tuple((s.shape, str(s.dtype)) for s in specs)


# ----------------------------------------------------------------------
# capacity planning (shared with the static capacity analyzer)
# ----------------------------------------------------------------------
def plan_capacities(dag: WorkloadDAG, stats, view_infos, *,
                    safety: float = 4.0, cap_planner=None, ests=None,
                    carry_caps: dict | None = None, content_keys=None):
    """Plan per-node buffer capacities and static lowering specs.

    Returns (caps, scan_specs, join_specs, demands):
      caps:    planned output capacity class per node (0 where unsized),
      scan_specs[nid] = (idx_name, prefix, residual, takes, self_eq),
      join_specs[nid] = (lcol, rcol, residual, keep_right),
      demands: estimated row demand each sized buffer must absorb — the
               quantity `capacity_for` was fed, kept so the static
               capacity analyzer can re-check headroom without
               re-deriving the sizing inputs.
    """
    if ests is None:
        ests = cost_mod.estimate_dag(dag, stats, view_infos)
    if content_keys is None and carry_caps:
        content_keys = dag.content_keys()

    def _cap(node, rows: float) -> int:
        if cap_planner is not None:
            planned = int(cap_planner(node.plan, rows))
        else:
            planned = cost_mod.capacity_for(rows, safety=safety)
        if carry_caps:
            planned = max(planned,
                          carry_caps.get(content_keys[node.id], 0))
        return planned

    caps = [0] * len(dag.nodes)
    demands = [0.0] * len(dag.nodes)
    scan_specs: dict[int, tuple] = {}
    join_specs: dict[int, tuple] = {}
    for node in dag.nodes:
        if node.kind == "scan":
            idx_name, prefix, residual, takes, self_eq, _sorted = \
                E.atom_scan_spec(node.spec)
            scan_specs[node.id] = (idx_name, prefix, residual, takes,
                                   self_eq)
            demands[node.id] = E.range_cardinality(node.spec, prefix, stats)
            caps[node.id] = _cap(node, demands[node.id])
        elif node.kind == "join":
            lid, rid = node.child_ids
            pairs = node.spec
            lead_k, demands[node.id] = cost_mod.join_lead(
                pairs, ests[lid].info, ests[rid].info)
            lcol, rcol = pairs[lead_k]
            residual = tuple(p for k, p in enumerate(pairs)
                             if k != lead_k)
            drop = {r for _, r in pairs}
            keep_right = tuple(i for i in range(dag.nodes[rid].width)
                               if i not in drop)
            join_specs[node.id] = (lcol, rcol, residual, keep_right)
            caps[node.id] = _cap(node, demands[node.id])
    return caps, scan_specs, join_specs, demands


# ----------------------------------------------------------------------
# the bucketed program
# ----------------------------------------------------------------------
class BucketedProgram:
    """Executable lowering of a `WorkloadDAG` as shape buckets.

    `execute(tt, views)` runs every bucket in wave order — one AOT
    compiled `lax.scan` dispatch per bucket — and returns
    ({root name: PRel}, own_overflow np (n_nodes,)): the members'
    results plus host-side overflow attribution.

    `promote(node_ids)` moves the offending nodes' buckets to the next
    capacity class; only those buckets' bodies (and consumers whose
    operand shapes changed) recompile on the next execute — everything
    else hits the persistent cache.

    `role` names what the program is for ("workload": the serving
    program; "delta": the maintainer's insert-delta program;
    "materialize": device materialization).  It names every bucket body
    (`body_builder`) and is part of each compile-cache key, so a body
    compiled under one name is never served under another.

    `tt_rows` (the TT index operand's row count) and `view_caps` (view
    id -> buffer capacity) are the static shapes the program will run
    on, which decide the joins lowered to probes (`plan_probes`); left
    out, they are estimated from `stats` and the view estimates.
    """

    def __init__(self, dag: WorkloadDAG, stats, view_infos, *,
                 safety: float = 4.0, use_pallas: bool = False,
                 cap_planner=None, ests=None,
                 carry_caps: dict | None = None, role: str = "workload",
                 tt_rows: int | None = None,
                 view_caps: dict[int, int] | None = None):
        self.dag = dag
        self.stats = stats
        self.use_pallas = use_pallas
        self.role = role
        if ests is None:
            ests = cost_mod.estimate_dag(dag, stats, view_infos)
        self.ests = ests
        self.content_keys = dag.content_keys()
        caps, scan_specs, join_specs, demands = plan_capacities(
            dag, stats, view_infos, safety=safety, cap_planner=cap_planner,
            ests=ests, carry_caps=carry_caps,
            content_keys=self.content_keys)
        self.caps = caps
        self.demands = demands
        if tt_rows is None:
            tt_rows = max(int(stats.n_triples), 1)
        self.probes = plan_probes(dag, caps, scan_specs, join_specs,
                                  self.static_eff_caps(view_caps), tt_rows)
        self.buckets, self.node_bucket = plan_buckets(
            dag, caps, scan_specs, join_specs, self.probes)
        # stack per-member pattern constants once (they never change);
        # device-resident once: re-uploading per run would put a host
        # transfer on every dispatch of the hot path
        def stack(rows, b):
            return jnp.asarray(np.asarray(rows, np.int32).reshape(
                len(b.node_ids), -1))

        for b in self.buckets:
            if b.kind == "scan":
                b.pvals = stack([[v for _, v in scan_specs[nid][1]]
                                 for nid in b.node_ids], b)
                b.rvals = stack([[v for _, v in scan_specs[nid][2]]
                                 for nid in b.node_ids], b)
            elif b.kind == "probe":
                b.pvals = stack([[v for _, v in self.probes[nid][1]]
                                 for nid in b.node_ids], b)
        # telemetry (per program; the cache itself is process-global)
        self.cache_hits = 0
        self.cache_misses = 0
        self.compile_seconds = 0.0
        self.compile_log: list[dict] = []  # one entry per body compile

    # ------------------------------------------------------------------
    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    def signatures(self) -> set[tuple]:
        return {(b.static, b.cap) for b in self.buckets}

    # ------------------------------------------------------------------
    def promote(self, node_ids) -> list[tuple[int, int, int]]:
        """Promote the buckets containing `node_ids` to the next
        capacity class.  Returns [(nid, old_cap, new_cap)] for every
        member of every promoted bucket (the whole bucket moves, so the
        batch stays shape-uniform); empty when every offending bucket is
        already at the capacity ceiling."""
        grown: list[tuple[int, int, int]] = []
        seen: set[int] = set()
        for nid in node_ids:
            bucket = self.node_bucket.get(nid)
            if bucket is None or bucket.cap == 0 or id(bucket) in seen:
                continue
            seen.add(id(bucket))
            new = cost_mod.promote_capacity(bucket.cap, CAP_CEIL)
            if new <= bucket.cap:
                continue
            old = bucket.cap
            bucket.cap = new
            bucket.promotions += 1
            for m in bucket.node_ids:
                self.caps[m] = new
                grown.append((m, old, new))
        return grown

    # ------------------------------------------------------------------
    # operand assembly
    # ------------------------------------------------------------------
    @staticmethod
    def _pad_rows(data, cap: int):
        """Pad the row axis (second-to-last) up to `cap` with -1 rows;
        padded rows sit beyond the valid count, matching the scrubbed
        tail every operator already ignores."""
        have = data.shape[-2]
        if have == cap:
            return data
        widths = [(0, 0)] * data.ndim
        widths[-2] = (0, cap - have)
        return jnp.pad(data, widths, constant_values=-1)

    def _gather_slot(self, res, child_ids, cap: int):
        """Stack one operand slot for a bucket: the children's PRels,
        padded to `cap` rows each.  Consecutive children living in the
        same producer bucket collapse into one gather, so the dispatch
        count scales with producer-bucket runs, not members."""
        parts_d, parts_n, parts_o = [], [], []
        i = 0
        while i < len(child_ids):
            entry = res[child_ids[i]]
            if entry[0] is None:  # single PRel (view node)
                rel = entry[1]
                parts_d.append(self._pad_rows(rel.data[None], cap))
                parts_n.append(rel.n[None])
                parts_o.append(rel.overflow[None])
                i += 1
                continue
            producer = entry[0]
            idxs = [entry[1]]
            j = i + 1
            while j < len(child_ids) and res[child_ids[j]][0] is producer:
                idxs.append(res[child_ids[j]][1])
                j += 1
            take = jnp.asarray(np.asarray(idxs, np.int32))
            parts_d.append(self._pad_rows(producer.data[take], cap))
            parts_n.append(producer.n[take])
            parts_o.append(producer.overflow[take])
            i = j
        if len(parts_d) == 1:
            return parts_d[0], parts_n[0], parts_o[0]
        return (jnp.concatenate(parts_d), jnp.concatenate(parts_n),
                jnp.concatenate(parts_o))

    # ------------------------------------------------------------------
    def _run_bucket(self, bucket: Bucket, tt, res, eff_cap):
        dag = self.dag
        build = lambda: body_builder(bucket, self.use_pallas, self.role)
        if bucket.kind == "scan":
            _, idx_name = bucket.static[0], bucket.static[1]
            args = (tt[idx_name], bucket.pvals, bucket.rvals)
            out_cap = bucket.cap
        elif bucket.kind == "filter":
            kids = [dag.nodes[nid].child_ids[0] for nid in bucket.node_ids]
            cap = max(eff_cap[c] for c in kids)
            cd, cn, co = self._gather_slot(res, kids, cap)
            vals = jnp.asarray(np.asarray(
                [dag.nodes[nid].spec[1] for nid in bucket.node_ids],
                np.int32))
            args = (cd, cn, co, vals)
            out_cap = cap
        elif bucket.kind == "join":
            lkids = [dag.nodes[nid].child_ids[0] for nid in bucket.node_ids]
            rkids = [dag.nodes[nid].child_ids[1] for nid in bucket.node_ids]
            lcap = max(eff_cap[c] for c in lkids)
            rcap = max(eff_cap[c] for c in rkids)
            ld, ln, lo = self._gather_slot(res, lkids, lcap)
            rd, rn, ro = self._gather_slot(res, rkids, rcap)
            args = (ld, ln, lo, rd, rn, ro)
            out_cap = bucket.cap
        elif bucket.kind == "probe":
            lkids = [dag.nodes[nid].child_ids[0] for nid in bucket.node_ids]
            lcap = max(eff_cap[c] for c in lkids)
            ld, ln, lo = self._gather_slot(res, lkids, lcap)
            args = (ld, ln, lo, tt[bucket.static[1]], bucket.pvals)
            out_cap = bucket.cap
        elif bucket.kind == "project":
            kids = [dag.nodes[nid].child_ids[0] for nid in bucket.node_ids]
            cap = max(eff_cap[c] for c in kids)
            cd, cn, co = self._gather_slot(res, kids, cap)
            args = (cd, cn, co)
            out_cap = cap
        else:
            raise TypeError(bucket.kind)

        specs = _specs_of(args)
        key = self.cache_key(bucket, specs)
        compiled, cached, dt = _CACHE.get(key, build, specs)
        if cached:
            self.cache_hits += 1
        else:
            self.cache_misses += 1
            self.compile_seconds += dt
            self.compile_log.append({
                "bucket": bucket.label, "kind": bucket.kind,
                "wave": bucket.wave, "cap": bucket.cap,
                "batch": len(bucket.node_ids), "seconds": dt,
            })
        out = compiled(*args)
        for i, nid in enumerate(bucket.node_ids):
            res[nid] = (out, i)
            eff_cap[nid] = out_cap
        return out

    # ------------------------------------------------------------------
    def execute(self, tt, views):
        """Run every bucket; returns ({root: PRel}, own_overflow np)."""
        dag = self.dag
        n = len(dag.nodes)
        res: list = [None] * n
        eff_cap: list[int] = [0] * n
        view_nids: list[int] = []
        for node in dag.nodes:
            if node.kind == "view":
                rel = views[node.spec]
                res[node.id] = (None, rel)
                eff_cap[node.id] = rel.cap
                view_nids.append(node.id)
        outs = []
        for b in self.buckets:
            with trace.span(_BUCKET_SPANS[b.kind]):
                outs.append(self._run_bucket(b, tt, res, eff_cap))

        # host-side overflow attribution: one transfer for all flags
        flat = jax.device_get(
            [o.overflow for o in outs]
            + [res[nid][1].overflow for nid in view_nids])
        raw = np.zeros(n, dtype=bool)
        for b, ovf in zip(self.buckets, flat[: len(outs)]):
            raw[np.asarray(b.node_ids)] = np.asarray(ovf)
        for nid, ovf in zip(view_nids, flat[len(outs):]):
            raw[nid] = bool(ovf)
        own = raw.copy()
        for node in dag.nodes:
            if node.kind == "view":
                own[node.id] = False
            elif node.child_ids and raw[list(node.child_ids)].any():
                own[node.id] = False  # inherited, not this node's buffer

        roots: dict[str, E.PRel] = {}
        for name, nid in dag.roots.items():
            entry = res[nid]
            if entry[0] is None:
                roots[name] = entry[1]
            else:
                out, i = entry
                roots[name] = E.PRel(out.data[i], out.n[i], out.overflow[i])
        return roots, own

    # ------------------------------------------------------------------
    # static views of the program (no execution) — jaxpr lint hooks
    # ------------------------------------------------------------------
    def static_eff_caps(self, view_caps: dict[int, int] | None = None
                        ) -> list[int]:
        """Effective buffer capacity per node, computed exactly like
        `execute` propagates it but without touching the device: views
        take `view_caps[vid]` (falling back to a capacity class planned
        from the estimated extent rows), scans/joins their capacity
        class (promotions included), filters/projects the max of their
        child caps."""
        view_caps = view_caps or {}
        eff: list[int] = [0] * len(self.dag.nodes)
        for node in self.dag.nodes:  # children come before their parents
            if node.kind == "view":
                eff[node.id] = view_caps.get(
                    node.spec,
                    cost_mod.capacity_for(self.ests[node.id].rows,
                                          safety=1.0))
            elif node.kind in ("scan", "join"):
                eff[node.id] = self.caps[node.id]
            else:  # filter/project pass through their child's cap
                eff[node.id] = max(eff[c] for c in node.child_ids)
        return eff

    def abstract_args(self, bucket: Bucket, n_tt: int,
                      eff_cap: list[int]) -> tuple:
        """ShapeDtypeStructs of the operands `_run_bucket` would stack
        for this bucket — enough to trace the body with `make_jaxpr` /
        `eval_shape` without any device data.  `n_tt` is the triple
        count (scan and probe buckets read one sorted (n_tt, 3) index)."""
        dag = self.dag
        B = len(bucket.node_ids)
        i32, b1 = np.dtype(np.int32), np.dtype(bool)

        def slot(kids, cap: int, width: int) -> tuple:
            return (jax.ShapeDtypeStruct((B, cap, width), i32),
                    jax.ShapeDtypeStruct((B,), i32),
                    jax.ShapeDtypeStruct((B,), b1))

        if bucket.kind == "scan":
            pw = 0 if bucket.pvals is None else bucket.pvals.shape[1]
            rw = 0 if bucket.rvals is None else bucket.rvals.shape[1]
            return (jax.ShapeDtypeStruct((n_tt, 3), i32),
                    jax.ShapeDtypeStruct((B, pw), i32),
                    jax.ShapeDtypeStruct((B, rw), i32))
        if bucket.kind == "probe":
            kids = [dag.nodes[nid].child_ids[0] for nid in bucket.node_ids]
            cap = max(eff_cap[c] for c in kids)
            return slot(kids, cap, bucket.static[-1]) + (
                jax.ShapeDtypeStruct((n_tt, 3), i32),
                jax.ShapeDtypeStruct(bucket.pvals.shape, i32))
        if bucket.kind == "filter":
            kids = [dag.nodes[nid].child_ids[0] for nid in bucket.node_ids]
            cap = max(eff_cap[c] for c in kids)
            _, _ci, width = bucket.static
            return slot(kids, cap, width) + (
                jax.ShapeDtypeStruct((B,), i32),)
        if bucket.kind == "join":
            lkids = [dag.nodes[nid].child_ids[0] for nid in bucket.node_ids]
            rkids = [dag.nodes[nid].child_ids[1] for nid in bucket.node_ids]
            lcap = max(eff_cap[c] for c in lkids)
            rcap = max(eff_cap[c] for c in rkids)
            lw, rw = bucket.static[5], bucket.static[6]
            return slot(lkids, lcap, lw) + slot(rkids, rcap, rw)
        if bucket.kind == "project":
            kids = [dag.nodes[nid].child_ids[0] for nid in bucket.node_ids]
            cap = max(eff_cap[c] for c in kids)
            cw = bucket.static[3]
            return slot(kids, cap, cw)
        raise TypeError(bucket.kind)

    def cache_key(self, bucket: Bucket, specs) -> tuple:
        """The persistent-cache key `_run_bucket` would use for this
        bucket with operands of `specs` shapes (lint checks hashability
        and cross-bucket collision-freedom of exactly these keys)."""
        return (self.role, bucket.static, bucket.cap, self.use_pallas,
                _shape_key(specs))

    # ------------------------------------------------------------------
    def telemetry(self) -> dict:
        return {
            "buckets": self.n_buckets,
            "bucket_signatures": len(self.signatures()),
            "bucket_compiles": self.cache_misses,
            "bucket_cache_hits": self.cache_hits,
            "bucket_cache_misses": self.cache_misses,
            "bucket_compile_seconds": self.compile_seconds,
            "bucket_compile_log": list(self.compile_log),
            "bucket_promotions": sum(b.promotions for b in self.buckets),
            "probe_joins": len(self.probes),
        }
