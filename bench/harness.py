"""One run of one cell: set up, drive the window, check, report.

Everything a cell needs is found by name from `BENCHMARK.json`: its
configuration file, its traffic mix (`bench/traffic/<traffic>.json`),
its offered rate (`bench/cells/<cell>.json`), the configuration's data
generator and plain reference (`bench/generators/<g>.py`,
`bench/references/<r>.py`), and one reader per metric
(`bench/metrics/<metric>.py`, a `read(ctx)` that returns a number or
None when it finds nothing to read).

The system under test is driven through its public serving path:
`TuningSession.retune()` / `apply()`, `serve(maintenance=...)`, then
`QueryServer.submit` and `QueryServer.answer_batch`.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import schedule as S
from bench.driver import Driver

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def start_jax(who: str, chips: int) -> bool:
    """Keep JAX's compilation cache in `.jax_cache/` at the checkout
    root, so only a checkout's first run compiles, and look for `chips`
    TPU chips: False, with a message, where JAX finds fewer."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"bench: {who} needs {chips} TPU chip(s), JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return False
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return True


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """A cell's entry, configuration, mix and offered rate."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = bench or load_benchmark()
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = by_name[name]
        conf = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.cfg = load_json(os.path.join(ROOT, conf["file"]))
        self.mix = load_json(os.path.join(BENCH, "traffic",
                                          self.entry["traffic"] + ".json"))
        self.data = load_json(os.path.join(BENCH, "cells", name + ".json"))
        self.rate = float(self.data["ops_per_s"])
        self.chips = int(self.entry["chips"])
        self.metrics = {}
        for kind in ("end_to_end", "per_layer"):
            self.metrics[kind] = [
                m for m in bench[kind]
                if name in m.get("workloads", [name])]


class CompileCounter:
    """Counts JAX backend-compile events, and their seconds, by phase."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.in_window = 0
        self.window_open = False

    def __call__(self, event: str, seconds: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.seconds += seconds
            self.count += 1
            if self.window_open:
                self.in_window += 1


class Context:
    """What metric readers read."""

    def __init__(self, rec, setup_s, compiles_in_window, trace, appends,
                 device_kind, peaks):
        self.rec = rec
        self.setup_s = setup_s
        self.compiles_in_window = compiles_in_window
        self.trace = trace
        self.appends = appends
        self.device_kind = device_kind
        self._peaks = peaks

    def peak(self, key: str) -> float:
        devices = self._peaks["devices"]
        if self.device_kind not in devices:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           f"in bench/peaks.json")
        return float(devices[self.device_kind][key])

    def read_latencies_ms(self) -> list[float]:
        """Every read due in the window; one never served counts from
        its due time to the end of the run."""
        rec = self.rec
        return [((d if d is not None else rec.drain_end) - due) * 1e3
                for due, d in zip(rec.due, rec.done)]

    def update_visible_ms(self) -> list[float]:
        rec = self.rec
        return [(v if v is not None else rec.drain_end - due) * 1e3
                for due, v in zip(rec.update_due, rec.visible)]


def _program_objects(cell: Cell, uni, gen):
    """The configuration as the program takes it: store, schema, queries."""
    from repro.api import QualityWeights, SearchConfig, WizardConfig
    from repro.core.queries import CQ, Atom, Const, Var
    from repro.rdf.schema import RDFSchema
    from repro.rdf.triples import TripleStore

    V = uni.vocab
    sch = gen.schema_ids(V)
    schema = RDFSchema()
    for c, p in sch["subclass"]:
        schema.add_subclass(c, p)
    for c, p in sch["subprop"]:
        schema.add_subprop(c, p)
    for p, c in sch["domain"].items():
        schema.set_domain(p, c)
    for p, c in sch["range"].items():
        schema.set_range(p, c)

    def term(t: str):
        return Var(t[1:]) if t.startswith("?") else Const(V[t])

    queries = [CQ(tuple(term(v) for v in q["head"]),
                  tuple(Atom(*(term(t) for t in a)) for a in q["atoms"]),
                  name=q["name"], weight=float(q["weight"]))
               for q in cell.cfg["queries"]]
    t = cell.cfg["tuner"]
    wcfg = WizardConfig(search=SearchConfig(
        strategy=t["strategy"], max_states=t["max_states"],
        weights=QualityWeights(w_exec=t["w_exec"], w_maint=t["w_maint"],
                               w_space=t["w_space"])))
    return TripleStore(uni.triples), schema, queries, wcfg


def _sample_reads(sched: S.Schedule, seed: int, per_template: int) -> set[int]:
    """Reads whose answers are compared: `per_template` of each
    template, drawn from the seed, so the largest answers are in it."""
    rng = np.random.default_rng([seed, 2])
    by_name: dict[str, list[int]] = {}
    for r in sched.reads:
        by_name.setdefault(r.name, []).append(r.index)
    keep: set[int] = set()
    for name in sorted(by_name):
        idx = by_name[name]
        keep |= set(rng.choice(idx, min(per_template, len(idx)),
                               replace=False).tolist())
    return keep


@contextlib.contextmanager
def _traced(server, appends: list, flag):
    """Host spans around the layers' entry points, and a record of each
    `scatter_append` call's (rows, width), while the trace is on."""
    import jax
    from repro.kernels import ops as kops

    ex, mt = server.executor, server.maintainer
    orig_sa = kops.scatter_append

    def wrap(fn, name):
        def inner(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return inner

    def scatter_append(buf, n, rows, k):
        if flag():
            appends.append((int(k), int(buf.shape[1])))
        return orig_sa(buf, n, rows, k)

    ex.answer_workload = wrap(ex.answer_workload, "bench.fused_program")
    if mt is not None:
        mt.apply = wrap(mt.apply, "bench.maintenance_pass")
    kops.scatter_append = scatter_append
    try:
        yield
    finally:
        kops.scatter_append = orig_sa
        del ex.answer_workload
        if mt is not None:
            del mt.apply


def run_cell(name: "str | Cell", seed: int, seconds: float, trace: bool = False,
             t_start: float | None = None, maintenance: dict | None = None,
             fault=None) -> dict:
    """One run of cell `name` (or of a `Cell` a caller has adjusted).
    `maintenance` overrides the configuration's maintenance
    settings and `fault(server)` may break the served path: both are for
    the control and the tests of the check, never for a measured run."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    cell = name if isinstance(name, Cell) else Cell(name)
    cfg = cell.cfg
    gen = importlib.import_module(f"bench.generators.{cfg['generator']}")
    refmod = importlib.import_module(f"bench.references.{cfg['reference']}")
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        return _run(cell, gen, refmod, counter, seed, seconds, trace,
                    t_start, maintenance, fault)
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)


def _phase(name: str, t0: float, counter: CompileCounter, c0: float) -> None:
    log(f"setup {name}: {time.perf_counter() - t0:.3f} s, of which compile "
        f"{counter.seconds - c0:.3f} s")


class Served:
    """A cell set up and warmed: the store, the schedule and the server."""

    def __init__(self, uni, sched, server, names, weights, setup_s):
        self.uni, self.sched, self.server = uni, sched, server
        self.names, self.weights, self.setup_s = names, weights, setup_s


def set_up(cell, gen, counter, seed, seconds, t_start, maintenance=None,
           rate=None, fault=None, pool=None) -> Served:
    """Everything before the window: the data and schedule from the seed,
    retune, apply, and a warm-up of one read batch after an insert pass
    and after a delete pass, which leave the store as it was."""
    from repro.api import MaintenanceConfig, TuningSession

    cfg, mix = cell.cfg, cell.mix
    rate = cell.rate if rate is None else rate
    names = [q["name"] for q in cfg["queries"]]
    weights = {q["name"]: float(q["weight"]) for q in cfg["queries"]}

    t, c = time.perf_counter(), counter.seconds
    pool = pool or S.enrolment_ids_needed(mix, rate, seconds)
    uni = gen.generate(cfg, [seed, 0], pool)
    sched = S.build(mix, rate, seconds, seed, uni, gen, weights)
    store, schema, queries, wcfg = _program_objects(cell, uni, gen)
    _phase(f"generate ({len(uni.triples)} triples, {len(sched.reads)} "
           f"reads, {len(sched.batches)} update batches)", t, counter, c)

    t, c = time.perf_counter(), counter.seconds
    session = TuningSession(store, queries, schema=schema,
                            type_id=uni.vocab["rdf:type"], cfg=wcfg)
    session.retune()
    _phase("retune", t, counter, c)
    t, c = time.perf_counter(), counter.seconds
    session.apply(warm=True)
    _phase(f"apply ({len(session.executor.state.views)} views)", t, counter,
           c)

    t, c = time.perf_counter(), counter.seconds
    mcfg = dict(cfg["maintenance"], **(maintenance or {}))
    server = session.serve(maintenance=MaintenanceConfig(**mcfg))
    for ins, dels in sched.warmup:
        server.submit(inserts=ins, deletes=dels)
        server.answer_batch(names)
    server.answer_batch(names)
    if fault is not None:
        fault(server)
    _phase(f"warm-up ({server.stats.refreshes} maintenance passes)", t,
           counter, c)
    setup_s = time.perf_counter() - t_start
    log(f"setup total: {setup_s:.3f} s, of which compile "
        f"{counter.seconds:.3f} s in {counter.count} compiles")
    return Served(uni, sched, server, names, weights, setup_s)


def _run(cell, gen, refmod, counter, seed, seconds, trace, t_start,
         maintenance, fault) -> dict:
    import jax

    mix = cell.mix
    sv = set_up(cell, gen, counter, seed, seconds, t_start, maintenance,
                fault=fault)
    uni, sched, server, names = sv.uni, sv.sched, sv.server, sv.names
    setup_s, cfg = sv.setup_s, cell.cfg

    keep = _sample_reads(sched, seed, int(mix["check_reads_per_template"]))
    appends: list[tuple[int, int]] = []
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    span = (jax.profiler.TraceAnnotation if trace
            else (lambda _n: contextlib.nullcontext()))
    drv = Driver(server, sched, mix["batching"], keep=keep, span=span)
    reduced = None
    stats0 = server.stats.as_dict()
    try:
        with (_traced(server, appends, lambda: counter.window_open)
              if trace else contextlib.nullcontext()):
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.host_tracer_level = 1
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            counter.window_open = True
            # what compiles in the window (the program compiles a slice per
            # new result length) is not kept in the persistent cache, so a
            # run does not find programs that an earlier run of the same
            # seed compiled in its window
            keep_min = jax.config.jax_persistent_cache_min_compile_time_secs
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              float("inf"))
            try:
                rec = drv.run(seconds, drain_name=names[0],
                              on_close=lambda: setattr(counter,
                                                       "window_open", False))
            finally:
                counter.window_open = False
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", keep_min)
                if trace:
                    jax.profiler.stop_trace()
        if trace:
            from bench import trace_reduce

            reduced = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    stats1 = server.stats.as_dict()
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    log(f"window: {len(rec.batches)} read batches, "
        f"{stats1['refreshes'] - stats0['refreshes']} maintenance passes, "
        f"closed at {rec.end:.3f} s, drained at {rec.drain_end:.3f} s; "
        f"generator lateness max "
        f"{max(rec.lateness, default=0.0) * 1e3:.3f} ms; "
        f"{counter.in_window} compiles in the window")

    # the program's state goes before the reference runs
    sv = server = drv = None
    gc.collect()
    t = time.perf_counter()
    checks = _check(rec, sched, uni, cfg, refmod)
    log(f"check: {len(rec.kept)} answers compared in "
        f"{time.perf_counter() - t:.3f} s")

    ctx = Context(rec, setup_s, counter.in_window, reduced, appends,
                  device["kind"], load_json(os.path.join(BENCH,
                                                         "peaks.json")))
    metrics = {}
    for m in cell.metrics["per_layer" if trace else "end_to_end"]:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    failed = sum(v["value"] for v in checks.values())
    out = {"correct": all(v["value"] <= v["limit"] for v in checks.values()),
           "attempted": len(sched.reads) + len(sched.batches),
           "failed": int(failed), "metrics": metrics, "device": device}
    if reduced is not None:
        from bench import trace_reduce

        out["breakdown"] = trace_reduce.breakdown(reduced)
    out["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    return out


def _check(rec, sched, uni, cfg, refmod) -> dict:
    """The numbers that decide `correct`, each with its limit."""
    unserved = sum(d is None for d in rec.done)
    bad = sum(b and d is not None for b, d in zip(rec.bad, rec.done))
    unapplied = sum(v is None for v in rec.visible)
    ref = refmod.Reference(uni.triples, cfg["queries"])
    wrong = 0
    version = 0
    for idx in sorted(rec.kept, key=lambda i: (rec.version[i], i)):
        while version < rec.version[idx]:
            b = sched.batches[version]
            ref.apply(b.inserts, b.deletes)
            version += 1
        want = ref.answer(sched.reads[idx].name)
        ans = rec.kept[idx]
        got = (np.unique(refmod.pack_rows(np.asarray(list(ans), np.int64)))
               if ans else np.zeros(0, np.uint64))
        if not np.array_equal(got, want):
            wrong += 1
            log(f"check: read {idx} ({sched.reads[idx].name}, version "
                f"{rec.version[idx]}) served {len(got)} rows, the "
                f"reference has {len(want)}")
    return {"wrong_answers": {"value": wrong, "limit": 0},
            "bad_tier_or_stale": {"value": bad, "limit": 0},
            "unserved_reads": {"value": unserved, "limit": 0},
            "unapplied_updates": {"value": unapplied, "limit": 0}}
