"""In-program spans at the layer boundaries of the serve and maintenance path.

    from repro import trace
    trace.enable()
    ...                       # serve, submit, answer
    trace.disable()
    print(trace.summary())

Off (the default), `span(name)` checks one module global and returns one
shared `contextlib.nullcontext()`: it allocates and records nothing.  On,
each span

  * opens a `jax.profiler.TraceAnnotation(name)`, so it lands on the host
    plane of any running profiler trace, on the device ops' clock;
  * appends a `Record` to a ring of `MAXLEN` records (what falls out
    is counted in `dropped`);
  * adds to per-name aggregates that drop nothing: count, total ns and
    self ns (the duration less the time of its child spans).

Every record carries `batch`: the id of the `answer_batch` span that
caused the work (a span opened with `new_batch=True`), or None outside
one.  `enable()` also counts JAX backend compiles under the innermost
open span of the compiling thread, or under "outside".

Spans time host work only: none synchronises with the device, so a span
around a dispatch measures the dispatch, and one around a transfer the
wait for the device plus the copy.  Program span names start with
`rdfviews.`.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque
from typing import NamedTuple

import jax
from jax.profiler import TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
OUTSIDE = "outside"
MAXLEN = 1 << 16         # records each ring keeps


class Record(NamedTuple):
    name: str
    start_ns: int           # time.perf_counter_ns: for durations only
    end_ns: int
    span_id: int
    parent_id: int | None
    batch: int | None       # the answer_batch that caused the work
    seq: int | None = None  # an update's sequence number (queued waits)


_NULL = contextlib.nullcontext()
_on = False
_lock = threading.Lock()
_local = threading.local()
_ids = itertools.count(1)
_batches = itertools.count(1)
_ring: deque = deque(maxlen=MAXLEN)
_waits: deque = deque(maxlen=MAXLEN)
_dropped = 0
_agg: dict[str, list[int]] = {}      # name -> [count, total_ns, self_ns]
_compiles: dict[str, int] = {}


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def _keep(ring: deque, rec: Record, self_ns: int) -> None:
    global _dropped
    if len(ring) == ring.maxlen:
        _dropped += 1
    ring.append(rec)
    a = _agg.get(rec.name)
    if a is None:
        a = _agg[rec.name] = [0, 0, 0]
    a[0] += 1
    a[1] += rec.end_ns - rec.start_ns
    a[2] += self_ns


class _Span:
    __slots__ = ("name", "new_batch", "ann", "sid", "parent", "batch",
                 "prev_batch", "start", "child_ns")

    def __init__(self, name: str, new_batch: bool):
        self.name = name
        self.new_batch = new_batch

    def __enter__(self):
        st = _stack()
        self.parent = st[-1].sid if st else None
        self.prev_batch = getattr(_local, "batch", None)
        if self.new_batch:
            _local.batch = next(_batches)
        self.batch = getattr(_local, "batch", None)
        self.sid = next(_ids)
        self.child_ns = 0
        st.append(self)
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        dur = end - self.start
        if st:
            st[-1].child_ns += dur
        if self.new_batch:
            _local.batch = self.prev_batch
        with _lock:
            _keep(_ring, Record(self.name, self.start, end, self.sid,
                                self.parent, self.batch),
                  dur - self.child_ns)
        return False


def span(name: str, new_batch: bool = False):
    """A context manager timing `name`; `new_batch=True` opens a new
    batch id that every span and record inside it carries."""
    if not _on:
        return _NULL
    return _Span(name, new_batch)


def now_ns() -> int | None:
    """A `record()` start stamp while tracing is on, else None."""
    return time.perf_counter_ns() if _on else None


def record(name: str, start_ns: int, end_ns: int, *,
           seq: int | None = None) -> None:
    """Write a span after the fact (a queued wait, which no context
    manager can cover), under the current span and batch."""
    if not _on:
        return
    st = _stack()
    with _lock:
        _keep(_waits, Record(name, start_ns, end_ns, next(_ids),
                             st[-1].sid if st else None,
                             getattr(_local, "batch", None), seq),
              end_ns - start_ns)


def _on_compile(event: str, seconds: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    st = getattr(_local, "stack", None)
    where = st[-1].name if st else OUTSIDE
    with _lock:
        _compiles[where] = _compiles.get(where, 0) + 1


def enable() -> None:
    """Start recording, and counting backend compiles by span."""
    global _on
    if not _on:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _on = True


def disable() -> None:
    """Stop recording; what was recorded stays until `reset()`."""
    global _on
    if _on:
        _on = False
        jax.monitoring.unregister_event_duration_listener(_on_compile)


def reset() -> None:
    """Forget every record, aggregate and compile count."""
    global _dropped, _ring, _waits
    with _lock:
        _ring = deque(maxlen=MAXLEN)
        _waits = deque(maxlen=MAXLEN)
        _agg.clear()
        _compiles.clear()
        _dropped = 0


def summary() -> dict:
    """What was recorded: `aggregates` {name: {count, total_ns, self_ns}},
    `spans` and `waits` (the rings' records, as dicts),
    `compiles_by_span` {span name or "outside": count} and `dropped`."""
    with _lock:
        return {
            "aggregates": {n: {"count": a[0], "total_ns": a[1],
                               "self_ns": a[2]} for n, a in _agg.items()},
            "spans": [r._asdict() for r in _ring],
            "waits": [r._asdict() for r in _waits],
            "compiles_by_span": dict(_compiles),
            "dropped": _dropped,
        }
