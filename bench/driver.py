"""The open-loop driver: a schedule against a `QueryServer` on the wall clock.

Updates are submitted when they fall due (`QueryServer.submit`).  Reads
queue as they fall due and go out the way the serving frontend batches
them: at most `max_batch` per `answer_batch` call, a partial batch once
its oldest read has waited `batching_window_s`, one batch in flight.
Every read is timed from when it was due, so a stall delays the reads
behind it; every event's admission lateness is kept, so a starved
driver is not read as a fast server.

An update batch becomes visible with the return of the first
`answer_batch` that started after it was submitted and left no backlog
(the freshness guarantee applies the whole backlog before answering).
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field

from bench.schedule import Read, Schedule


@dataclass
class ReadBatch:
    start: float
    end: float
    reads: list[int]               # read indices
    maint_s: float                 # ServeStats.maintenance_seconds delta
    passes: int                    # ServeStats.refreshes delta


@dataclass
class Records:
    """What one window produced, times in seconds from its start."""

    due: list[float] = field(default_factory=list)          # per read
    dispatch: list[float | None] = field(default_factory=list)
    done: list[float | None] = field(default_factory=list)
    bad: list[bool] = field(default_factory=list)  # below tier 0 / stale
    version: list[int] = field(default_factory=list)
    update_due: list[float] = field(default_factory=list)
    visible: list[float | None] = field(default_factory=list)
    lateness: list[float] = field(default_factory=list)    # per event
    batches: list[ReadBatch] = field(default_factory=list)
    kept: dict[int, object] = field(default_factory=dict)  # read -> answer
    end: float = 0.0               # last read served (window close)
    drain_end: float = 0.0         # last update made visible


class Driver:
    def __init__(self, server, schedule: Schedule, batching: dict,
                 keep: set[int] = frozenset(), span=None,
                 clock=time.perf_counter, sleep=time.sleep,
                 give_up_s: float = 60.0):
        self.server = server
        self.s = schedule
        self.max_batch = int(batching["max_batch"])
        self.window = float(batching["batching_window_s"])
        self.keep = keep
        self.span = span or (lambda name: contextlib.nullcontext())
        self.clock = clock
        self.sleep = sleep
        self.give_up_s = give_up_s

    def run(self, seconds: float, drain_name: str, on_close=None) -> Records:
        s, srv = self.s, self.server
        rec = Records()
        rec.due = [r.due for r in s.reads]
        rec.dispatch = [None] * len(s.reads)
        rec.done = [None] * len(s.reads)
        rec.bad = [False] * len(s.reads)
        rec.version = [0] * len(s.reads)
        rec.update_due = [b.due for b in s.batches]
        rec.visible = [None] * len(s.batches)
        events, n = s.events, len(s.events)
        queue: deque[Read] = deque()
        pending: list = []
        submitted = 0
        i = 0
        t0 = self.clock()
        stop = seconds + self.give_up_s
        with self.span("bench.window"):
            while True:
                now = self.clock() - t0
                while i < n and events[i].due <= now:
                    ev = events[i]
                    i += 1
                    rec.lateness.append(now - ev.due)
                    if isinstance(ev, Read):
                        queue.append(ev)
                    else:
                        srv.submit(inserts=ev.inserts, deletes=ev.deletes)
                        submitted += 1
                        pending.append(ev)
                if now > stop:
                    break
                if queue:
                    ready = queue[0].due + self.window
                    if len(queue) >= self.max_batch or now >= ready:
                        take = [queue.popleft() for _ in
                                range(min(self.max_batch, len(queue)))]
                        pending = self._dispatch(rec, take, pending,
                                                 submitted, t0)
                        continue
                    wake = ready if i >= n else min(ready, events[i].due)
                elif i < n:
                    wake = events[i].due
                else:
                    break
                with self.span("bench.wait"):
                    self.sleep(max(0.0, wake - (self.clock() - t0)))
            rec.end = self.clock() - t0
        if on_close is not None:
            on_close()
        if pending and rec.end <= stop:
            # updates due in the window that no read has applied yet: one
            # more batch, not counted as a read, makes them visible
            self._dispatch(rec, [], pending, submitted, t0, drain_name)
        rec.drain_end = self.clock() - t0
        return rec

    def _dispatch(self, rec: Records, take: list[Read], pending: list,
                  version: int, t0: float, drain_name: str | None = None
                  ) -> list:
        srv = self.server
        st = srv.stats
        m0, r0 = st.maintenance_seconds, st.refreshes
        names = [r.name for r in take] or [drain_name]
        start = self.clock() - t0
        with self.span("bench.answer_batch"):
            out = srv.answer_batch(names)
        end = self.clock() - t0
        lb = st.last_batch
        bad = lb.get("tier") != 0 or bool(lb.get("stale")) \
            or bool(lb.get("degraded"))
        if take:
            rec.batches.append(ReadBatch(
                start, end, [r.index for r in take],
                st.maintenance_seconds - m0, st.refreshes - r0))
        for r, ans in zip(take, out):
            rec.dispatch[r.index] = start
            rec.done[r.index] = end
            rec.version[r.index] = version
            rec.bad[r.index] = bad or ans is None
            if r.index in self.keep:
                rec.kept[r.index] = ans
        if srv.stream is not None and srv.stream.pending_batches:
            return pending      # maintenance failed: nothing became visible
        for ev in pending:
            rec.visible[ev.index] = end - ev.due
        return []
