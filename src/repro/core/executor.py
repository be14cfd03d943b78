"""Query Executor: answers workload queries through the stored rewritings.

The production path is *workload-level*: every member rewriting
(including reformulation-group members) is canonicalized into one
shared-subplan DAG (`query/dag.py`) and lowered into one shape-bucketed
program (`query/buckets.py`, driven by `query/workload.py`) that answers
the entire workload in one pass — each shared subtree computed once.
Capacity overflows do not raise: the adaptive driver promotes the
offending bucket to the next capacity class and recompiles it under a
bounded retry budget (telemetry on `executor.workload`).

Paths with identical answers:
  * `answer(name)` / `answer_workload()` — the fused program over
    materialized padded views (adaptive, built once per workload),
  * `answer_group_per_query(name)` — each member's rewriting lowered
    alone through the same bucketed lowering (no shared subplans; the
    serving ladder's tier 1),
  * `answer_direct(name)` — oracle evaluation over the raw triple table
    (the paper's "before tuning" baseline).

Union groups from RDFS reformulation are answered by unioning member
rewritings (`answer_group`).  Disconnected rewritings (cartesian
products) are not device-compilable and fall back to the oracle over
the materialized extents.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro import trace
from repro.core.state import State
from repro.query import engine as E
from repro.query import ref_engine as R
from repro.query.dag import build_dag
from repro.query.plan import has_cartesian
from repro.query.workload import WorkloadExecutor
from repro.rdf.triples import TripleStore
from repro.views.materializer import materialize_state, materialize_state_device


@dataclass
class ExecutorSnapshot:
    """Everything `swap_state`/`refresh` mutate, captured by reference
    (dicts shallow-copied) so a failed hot swap restores the executor
    object in place — a server holding it keeps serving the previous
    program."""

    store: object
    state: State
    groups: dict
    queries: dict
    dag: object
    oracle_names: set
    extents: dict
    device_views: dict
    infos: dict
    tt: object
    workload: object
    results: dict | None


class QueryExecutor:
    def __init__(self, store: TripleStore, state: State,
                 groups: dict[str, list[str]] | None = None,
                 use_pallas: bool = False, safety: float = 4.0,
                 max_retries: int = 12, cap_planner=None,
                 device_materialize: bool = False,
                 fault_hook=None):
        self.fault_hook = fault_hook
        self.store = store
        self.state = state
        self.groups = groups or {q.name: [q.name] for q in state.queries}
        self._use_pallas = use_pallas
        self._safety = safety
        self._max_retries = max_retries
        self._cap_planner = cap_planner
        self._device_materialize = device_materialize
        self._queries = {q.name: q for q in state.queries}

        # ---- fused workload path: one DAG + one jitted program --------
        self._build_dag()
        self._load_device_state(store)

        # tier 1 of the serving ladder: one-member programs, built on
        # first use (a healthy server never builds them)
        self._per_query: dict[str, WorkloadExecutor] = {}

    def _build_dag(self) -> None:
        device_plans = {}
        self._oracle_names: set[str] = set()
        for name, plan in self.state.rewritings.items():
            if has_cartesian(plan):
                self._oracle_names.add(name)
            else:
                device_plans[name] = plan
        self.dag = build_dag(device_plans)

    def _load_device_state(self, store: TripleStore,
                           carry_caps: dict | None = None) -> None:
        """(Re)materialize views and upload TT indexes + rebuild the
        fused executor against them.  `carry_caps` seeds the new program
        with capacities a previous one learned adaptively."""
        self.store = store
        if self._device_materialize:
            self.extents, self.device_views, self.infos = \
                materialize_state_device(self.state, store,
                                         use_pallas=self._use_pallas)
        else:
            self.extents, self.device_views, self.infos = \
                materialize_state(self.state, store)
        self.tt = E.tt_device_indexes(store)
        self.workload = WorkloadExecutor(
            self.dag, store.stats, self.infos, safety=self._safety,
            use_pallas=self._use_pallas, max_retries=self._max_retries,
            cap_planner=self._cap_planner, carry_caps=carry_caps,
            fault_hook=self.fault_hook,
        )
        self._results: dict[str, np.ndarray] | None = None

    # ------------------------------------------------------------------
    # transactional binding snapshots
    # ------------------------------------------------------------------
    def snapshot(self) -> ExecutorSnapshot:
        """Capture every binding `swap_state`/`refresh` mutate."""
        return ExecutorSnapshot(
            store=self.store, state=self.state, groups=dict(self.groups),
            queries=dict(self._queries), dag=self.dag,
            oracle_names=set(self._oracle_names),
            extents=dict(self.extents), device_views=dict(self.device_views),
            infos=dict(self.infos), tt=self.tt, workload=self.workload,
            results=self._results)

    def restore(self, snap: ExecutorSnapshot) -> None:
        """Roll the executor back to a snapshot, in place."""
        self.store = snap.store
        self.state = snap.state
        self.groups = snap.groups
        self._queries = snap.queries
        self.dag = snap.dag
        self._oracle_names = snap.oracle_names
        self.extents = snap.extents
        self.device_views = snap.device_views
        self.infos = snap.infos
        self.tt = snap.tt
        self.workload = snap.workload
        self._results = snap.results
        self._per_query = {}

    def set_fault_hook(self, hook) -> None:
        """Attach a chaos injector to this executor and its current
        fused program (future programs inherit it automatically)."""
        self.fault_hook = hook
        self.workload.fault_hook = hook

    def refresh(self, store: TripleStore | None = None) -> None:
        """Point the executor at a maintained/replaced triple store:
        re-materializes every view extent, re-uploads the TT indexes,
        and recompiles the fused program against the fresh statistics.
        With no argument, refreshes device state from the current store
        (e.g. after in-place mutation).  Capacities the old program
        learned adaptively are carried into the new one.  Transactional:
        a failure mid-refresh restores the previous bindings."""
        snap = self.snapshot()
        carry = self.workload.learned_caps()
        try:
            self._load_device_state(
                store if store is not None else self.store,
                carry_caps=carry)
        except Exception:
            self.restore(snap)
            raise
        self._per_query = {}

    def swap_state(self, state: State,
                   groups: dict[str, list[str]] | None = None,
                   warm: bool = True) -> dict:
        """Online view swap onto a retuned configuration: diff old vs new
        views by canonical key, materialize ONLY the genuinely new
        extents (reusing surviving ones through a column permutation),
        drop dead extents, and hot-swap the compiled workload program.
        The executor object stays valid throughout — a server holding it
        keeps serving.

        Capacities the outgoing program learned adaptively are carried
        into the incoming one (keyed by DAG content key), so the fresh
        program does not re-learn overflows the old one already healed.
        With `warm=True` (default) the new program is pre-warmed before
        the swap returns: every bucket body is compiled (mostly
        persistent-cache hits) and the workload results are cached, so
        the serving path never pays a cold compile.  Returns the swap
        summary: {"materialized": [vid], "reused": [vid],
        "dropped": [prev_vid]}.

        The swap is TRANSACTIONAL: any failure — materialization,
        program construction, the pre-warm compile/run — rolls every
        binding back to the snapshot taken on entry and re-raises, so
        the executor object keeps serving the previous program.
        """
        from repro.views.materializer import materialize_state_delta

        snap = self.snapshot()
        carry = self.workload.learned_caps()
        try:
            extents, device, infos, reused, fresh, dropped = \
                materialize_state_delta(state, self.store, self.state,
                                        self.extents, self.infos,
                                        self.device_views)
            self.state = state
            self.groups = groups or {q.name: [q.name] for q in state.queries}
            self._queries = {q.name: q for q in state.queries}
            self.extents, self.device_views, self.infos = \
                extents, device, infos
            self._build_dag()
            self.workload = WorkloadExecutor(
                self.dag, self.store.stats, self.infos, safety=self._safety,
                use_pallas=self._use_pallas, max_retries=self._max_retries,
                cap_planner=self._cap_planner, carry_caps=carry,
                fault_hook=self.fault_hook,
            )
            self._results = None
            self._per_query = {}
            if warm:
                self.warmup()
        except Exception:
            self.restore(snap)
            raise
        return {"materialized": sorted(fresh), "reused": sorted(reused),
                "dropped": dropped}

    def note_maintenance(self, store: TripleStore) -> None:
        """In-place delta applied by `repro.maintenance.ViewMaintainer`:
        extents/device buffers/TT were updated under the executor, so
        point at the new store and drop cached answers.  The compiled
        workload program survives — maintenance keeps operand shapes in
        their capacity classes precisely so this is NOT a refresh()."""
        self.store = store
        self._results = None
        self._per_query = {}

    def warmup(self) -> None:
        """Compile every bucket body of the current program and cache
        the workload results, so the next `answer*` call is pure reads —
        the pre-warming half of the hot-swap contract."""
        roots = self.workload.warmup(self.tt, self.device_views)
        self._results = {name: E.to_numpy(rel) for name, rel in roots.items()}

    def per_query_program(self, member: str) -> WorkloadExecutor:
        """Tier 1's program for one non-oracle member: its rewriting
        lowered alone (no shared subplans) through the same bucketed
        lowering as the fused program, built on first use.  It carries
        no fault hook: injected faults target the fused program."""
        wl = self._per_query.get(member)
        if wl is None:
            wl = WorkloadExecutor(
                build_dag({member: self.state.rewritings[member]}),
                self.store.stats, self.infos, safety=self._safety,
                use_pallas=self._use_pallas, max_retries=self._max_retries,
                cap_planner=self._cap_planner, role="per_query")
            self._per_query[member] = wl
        return wl

    # ------------------------------------------------------------------
    def answer_workload(self) -> dict[str, np.ndarray]:
        """Answer every member rewriting in one fused device call
        (cached; overflow recovered adaptively)."""
        if self._results is None:
            with trace.span("rdfviews.query.fused_run"):
                roots = self.workload.run(self.tt, self.device_views)
                self._results = {name: E.to_numpy(rel)
                                 for name, rel in roots.items()}
        return self._results

    def answer(self, name: str) -> np.ndarray:
        """Answer one (possibly reformulated-member) query via its rewriting."""
        if name in self._oracle_names:
            return R.execute(self.state.rewritings[name], self.store,
                             self.extents).rows
        return self.answer_workload()[name]

    def answer_group(self, original_name: str) -> set[tuple[int, ...]]:
        """Union semantics over the reformulation members of a query."""
        out: set[tuple[int, ...]] = set()
        for member in self.groups[original_name]:
            out |= {tuple(r) for r in self.answer(member).tolist()}
        return out

    # ------------------------------------------------------------------
    def answer_group_per_query(self, original_name: str
                               ) -> set[tuple[int, ...]]:
        """Union-group answer with each member run alone — the serving
        ladder's first fallback when the fused program fails.  Each
        member runs its own one-member program (`per_query_program`; no
        shared subplans, overflow healed adaptively); cartesian members
        fall back to the oracle over the materialized extents as
        usual."""
        out: set[tuple[int, ...]] = set()
        for member in self.groups[original_name]:
            if member in self._oracle_names:
                rows = self.answer(member)
            else:
                rows = E.to_numpy(self.per_query_program(member).run(
                    self.tt, self.device_views)[member])
            out |= {tuple(r) for r in rows.tolist()}
        return out

    # ------------------------------------------------------------------
    def answer_direct(self, name: str) -> set[tuple[int, ...]]:
        """Baseline: evaluate the original CQ straight over the TT."""
        q = self._queries[name]
        return R.evaluate_cq(q, self.store).as_set()

    def answer_group_direct(self, original_name: str) -> set[tuple[int, ...]]:
        out: set[tuple[int, ...]] = set()
        for member in self.groups[original_name]:
            out |= self.answer_direct(member)
        return out

    # ------------------------------------------------------------------
    def telemetry(self) -> dict:
        t = self.workload.telemetry()
        t["oracle_fallbacks"] = len(self._oracle_names)
        return t
