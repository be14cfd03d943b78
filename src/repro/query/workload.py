"""Fused workload execution: the adaptive driver over `BucketedProgram`.

A `WorkloadDAG` (query/dag.py) is lowered by `query/buckets.py` into
shape buckets: DAG nodes grouped by (wave, operator kind, structural
signature, capacity class), each bucket executed as one `lax.scan` over
stacked operands, compiled ahead-of-time through a process-global
persistent cache.  Every shared node is computed once and its buffer
read by every consumer, and compile time scales with the number of
distinct shapes, not the number of queries — near-flat from 22 to 1000+
workload members (benchmarks/bench_compile_scale.py).  Static buffer
capacities are planned DAG-wide from the cost model
(`cost.estimate_dag` + `cost.capacity_for`).

`WorkloadExecutor` drives that program adaptively: alongside the root
results it observes each node's *own* overflow flag (latched overflow
minus anything inherited from children), so when a capacity proves too
small the driver knows exactly which buffer to grow.  An overflow
promotes only the offending node's *bucket* to the next capacity class
— the next execute recompiles that bucket's body (and any consumer
whose operand shape changed); every untouched body is a cache hit.
Capacities learned this way can be carried into a successor executor
(`learned_caps()` / `carry_caps=`), so a hot-swapped program does not
re-learn overflows the previous one already healed.

The fused path compiles scans without consumer-specific sort
preferences (a shared scan can't commit to one consumer's join order),
so joins never assume a pre-sorted build side here; correctness is
unaffected and the redundancy removed by sharing dominates the elided
sort it gives up.
"""
from __future__ import annotations

from typing import Callable

import numpy as np

from repro.query import cost as cost_mod
from repro.query import engine as E
from repro.query.buckets import BucketedProgram, compile_cache
from repro.query.cost import CAP_CEIL
from repro.query.dag import WorkloadDAG


class WorkloadExecutor:
    """Adaptive driver around the fused workload program.

    `run` executes the whole workload; on capacity overflow it promotes
    the offending *buckets* to the next capacity class, recompiles what
    changed, and retries — up to `max_retries` recompiles, after which
    (or once a buffer hits the capacity ceiling) it raises.

    `carry_caps` seeds planning with capacities a previous executor
    learned (`learned_caps()`), keyed by DAG content key, so a rebuilt
    program — e.g. after a `swap_state` hot swap — starts from the
    healed capacities instead of re-learning every overflow.
    """

    def __init__(self, dag: WorkloadDAG, stats, view_infos, *,
                 safety: float = 4.0, use_pallas: bool = False,
                 max_retries: int = 12,
                 cap_planner: Callable[[object, float], int] | None = None,
                 carry_caps: dict | None = None,
                 fault_hook=None, role: str = "workload"):
        # fault_hook: duck-typed chaos injector (`.fire(site)` raising an
        # injected fault when armed); None in production.  Sites fired
        # here: "compile" on program (re)construction, "device_call" and
        # "capacity_overflow" on each run.
        self.fault_hook = fault_hook
        # names the bucketed program's bodies (BucketedProgram's `role`)
        self.role = role
        self.dag = dag
        self.stats = stats
        self.view_infos = view_infos
        self.safety = safety
        self.use_pallas = use_pallas
        self.max_retries = max_retries
        self.cap_planner = cap_planner
        self.carry_caps = dict(carry_caps or {})
        self.caps: list[int] | None = None
        # telemetry
        self.compiles = 0
        self.runs = 0
        self.recompiles = 0
        self.cap_history: dict[int, list[int]] = {}
        self._prog: BucketedProgram | None = None
        self._ests = None

    # ------------------------------------------------------------------
    # program construction
    # ------------------------------------------------------------------
    def _ensure_ests(self):
        if self._ests is None:
            self._ests = cost_mod.estimate_dag(self.dag, self.stats,
                                               self.view_infos)
        return self._ests

    def _fire(self, site: str) -> None:
        if self.fault_hook is not None:
            self.fault_hook.fire(site)

    def _program(self, tt=None, views=None) -> BucketedProgram:
        """The bucketed program, lowered on first use against the shapes
        of the operands it is first run on (`tt`, `views`), where given."""
        if self._prog is None:
            self._fire("compile")
            self._prog = BucketedProgram(
                self.dag, self.stats, self.view_infos, safety=self.safety,
                use_pallas=self.use_pallas, cap_planner=self.cap_planner,
                ests=self._ensure_ests(), carry_caps=self.carry_caps,
                role=self.role,
                tt_rows=(next(iter(tt.values())).shape[0] if tt else None),
                view_caps=({vid: rel.cap for vid, rel in views.items()}
                           if views else None))
            self.caps = self._prog.caps
            self.compiles += 1
        return self._prog

    # ------------------------------------------------------------------
    def run(self, tt, views) -> dict[str, E.PRel]:
        """Answer every workload member; returns {name: PRel}."""
        self._fire("device_call")
        self._fire("capacity_overflow")
        prog = self._program(tt, views)
        attempt = 0
        while True:
            roots, own = prog.execute(tt, views)
            self.runs += 1
            if not own.any():
                return roots
            offending = np.nonzero(own)[0].tolist()
            if attempt >= self.max_retries:
                raise RuntimeError(
                    f"capacity overflow persists after {attempt} adaptive "
                    f"recompiles (nodes {offending}); estimates are "
                    f"pathologically low — raise max_retries or safety"
                )
            grown = prog.promote(offending)
            if not grown:
                raise RuntimeError(
                    f"capacity ceiling ({CAP_CEIL}) reached on nodes "
                    f"{offending}; result exceeds the engine's maximum "
                    f"buffer size"
                )
            for nid, old, new in grown:
                self.cap_history.setdefault(nid, [old]).append(new)
            self.compiles += 1
            self.recompiles += 1
            attempt += 1

    # ------------------------------------------------------------------
    # static verification
    # ------------------------------------------------------------------
    def analyze(self, n_tt: int | None = None, view_caps=None):
        """Run the static analyzers (IR verifier, capacity analysis,
        jaxpr lint) over this executor's DAG and its compiled-shape
        program, without executing anything.  Returns an
        `repro.analysis.AnalysisReport`."""
        from repro import analysis

        return analysis.analyze_workload(
            self.dag, self.stats, self.view_infos, program=self._program(),
            n_tt=n_tt, view_caps=view_caps)

    # ------------------------------------------------------------------
    # capacity carry across program rebuilds
    # ------------------------------------------------------------------
    def learned_caps(self) -> dict:
        """Capacities grown by the adaptive driver, keyed by DAG content
        key (stable across DAG instances), merged over whatever this
        executor itself was seeded with — pass to a successor's
        `carry_caps=` so a hot-swapped program keeps the healed sizes."""
        out = dict(self.carry_caps)
        if self.cap_history and self.caps is not None:
            keys = self.dag.content_keys()
            for nid in self.cap_history:
                out[keys[nid]] = max(out.get(keys[nid], 0), self.caps[nid])
        return out

    # ------------------------------------------------------------------
    def warmup(self, tt, views) -> dict[str, E.PRel]:
        """Pre-warm the serving path: compile every bucket body (mostly
        persistent-cache hits after a hot swap) and heal any planning
        overflows by running the workload once.  Returns the roots so
        callers can seed their result caches."""
        return self.run(tt, views)

    # ------------------------------------------------------------------
    def telemetry(self) -> dict:
        t = dict(self.dag.stats())
        t.update(compiles=self.compiles, runs=self.runs,
                 recompiles=self.recompiles,
                 grown_nodes=sorted(self.cap_history))
        # bucket/compile-cache telemetry (zeros until the program is
        # built, so consumers can rely on the keys being present)
        t.update(buckets=0, bucket_signatures=0, bucket_compiles=0,
                 bucket_cache_hits=0, bucket_cache_misses=0,
                 bucket_compile_seconds=0.0,
                 bucket_compile_log=[], bucket_promotions=0,
                 probe_joins=0)
        if self._prog is not None:
            t.update(self._prog.telemetry())
        t["compile_cache"] = compile_cache().stats()
        return t
