"""Metric arithmetic: percentiles over all requests, due-time latency
under a late driver, update visibility under coalesced passes, and the
`scatter_append` byte count.  The driver runs against a stand-in server
on a simulated clock."""
import math

import numpy as np
import pytest

from bench.driver import Driver
from bench.harness import Context
from bench.metrics import (maint_pass_ms, queue_wait_p95_ms,
                           read_p95_ms, scatter_append_roofline,
                           serve_ms_per_read, update_visible_p50_ms)
from bench.numbers import nearest_rank
from bench.schedule import Read, Schedule, UpdateBatch

BATCHING = {"max_batch": 16, "batching_window_s": 0.005}


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        # a real clock moves on while the driver sleeps, however briefly
        self.t += max(1e-6, s)


class Stats:
    def __init__(self):
        self.maintenance_seconds = 0.0
        self.refreshes = 0
        self.last_batch = {"tier": 0, "stale": False, "degraded": False}


class Stream:
    pending_batches = 0


class FakeServer:
    """Serves a batch in `read_s` per read, plus `pass_s` when updates
    are pending (one coalesced pass for the whole backlog)."""

    def __init__(self, clock, read_s=0.1, pass_s=1.0):
        self.clock, self.read_s, self.pass_s = clock, read_s, pass_s
        self.stats, self.stream = Stats(), Stream()
        self.pending = 0
        self.calls = []

    def submit(self, inserts=None, deletes=None):
        self.pending += 1

    def answer_batch(self, names):
        self.calls.append((self.clock(), list(names)))
        if self.pending:
            self.clock.sleep(self.pass_s)
            self.stats.maintenance_seconds += self.pass_s
            self.stats.refreshes += 1
            self.pending = 0
        self.clock.sleep(self.read_s * len(names))
        return [{(1,)} for _ in names]


def _schedule(reads, updates):
    events = sorted([Read(t, "q", i) for i, t in enumerate(reads)]
                    + [UpdateBatch(t, np.zeros((0, 3)), np.zeros((0, 3)), i)
                       for i, t in enumerate(updates)],
                    key=lambda e: e.due)
    return Schedule(events, [e for e in events if isinstance(e, Read)],
                    [e for e in events if isinstance(e, UpdateBatch)])


def _run(reads, updates, **kw):
    clock = Clock()
    srv = FakeServer(clock, **kw)
    s = _schedule(reads, updates)
    rec = Driver(srv, s, BATCHING, clock=clock, sleep=clock.sleep).run(
        10.0, drain_name="q")
    return Context(rec, 1.0, 0, None, [], "", {}), srv


def test_nearest_rank_over_all_values():
    xs = list(range(1, 101))
    assert nearest_rank(xs, 50) == 50
    assert nearest_rank(xs, 95) == 95
    assert nearest_rank([3.0], 95) == 3.0
    assert nearest_rank([5, 1, 4, 2, 3], 95) == 5
    assert nearest_rank([5, 1, 4, 2, 3], 50) == 3
    assert nearest_rank([], 50) is None
    # the p95 of 40 reads is the 38th smallest, over all of them
    lat = [10.0] * 37 + [1000.0, 2000.0, 3000.0]
    assert nearest_rank(lat, 95) == 1000.0
    assert nearest_rank(lat[1:], 95) == 2000.0


def test_latency_counts_from_due_time_when_the_server_falls_behind():
    # 20 reads due 10 ms apart; each read takes 100 ms to serve, so the
    # queue grows and later reads wait in it
    reads = [0.01 * i for i in range(20)]
    ctx, srv = _run(reads, [], read_s=0.1)
    rec = ctx.rec
    assert all(d is not None for d in rec.done)
    # the first batch holds only the first read (the next is due after
    # the 5 ms window closed); the rest queued behind it
    assert srv.calls[0][1] == ["q"]
    lat = ctx.read_latencies_ms()
    assert lat[0] == pytest.approx(5.0 + 100.0, abs=0.01)
    want = [(rec.done[i] - reads[i]) * 1e3 for i in range(20)]
    assert lat == pytest.approx(want)
    assert read_p95_ms.read(ctx) == pytest.approx(nearest_rank(want, 95))
    assert read_p95_ms.read(ctx) > 1000.0
    waits = [(rec.dispatch[i] - reads[i]) * 1e3 for i in range(20)]
    assert queue_wait_p95_ms.read(ctx) == pytest.approx(
        nearest_rank(waits, 95))


def test_a_late_driver_still_times_from_due():
    # a read due at 0.05 s is admitted only after a 0.5 s stall of the
    # driver's own: its latency includes the stall
    clock = Clock()
    srv = FakeServer(clock, read_s=0.01)
    s = _schedule([0.0, 0.05], [])
    real_sleep = clock.sleep
    stalls = iter([0.5])

    def stalled_sleep(x):
        real_sleep(x + next(stalls, 0.0))

    rec = Driver(srv, s, BATCHING, clock=clock, sleep=stalled_sleep).run(
        1.0, drain_name="q")
    assert max(rec.lateness) >= 0.45
    assert (rec.done[1] - rec.due[1]) >= 0.45


def test_update_visibility_under_a_coalesced_pass():
    # two update batches due while a read is being served are applied
    # together by the next read's batch: both become visible at its end
    ctx, srv = _run([0.0, 1.5], [0.2, 0.3], read_s=1.0, pass_s=0.5)
    rec = ctx.rec
    assert rec.visible[0] == pytest.approx(rec.done[1] - 0.2)
    assert rec.visible[1] == pytest.approx(rec.done[1] - 0.3)
    assert [b.passes for b in rec.batches] == [0, 1]
    assert update_visible_p50_ms.read(ctx) == pytest.approx(
        (rec.done[1] - 0.3) * 1e3)
    assert maint_pass_ms.read(ctx) == pytest.approx(500.0)
    # serving time less the pass, per read
    assert serve_ms_per_read.read(ctx) == pytest.approx(1000.0)


def test_updates_after_the_last_read_are_applied_by_a_drain_batch():
    ctx, srv = _run([0.0], [0.5], read_s=0.01, pass_s=0.2)
    rec = ctx.rec
    assert len(rec.batches) == 1            # the drain is not a read
    assert srv.calls[-1][0] >= 100.5
    assert rec.visible[0] == pytest.approx(rec.drain_end - 0.5)
    assert len(ctx.read_latencies_ms()) == 1


def test_scatter_append_byte_count():
    assert scatter_append_roofline.append_bytes(1, 1) == 8
    assert scatter_append_roofline.append_bytes(100, 3) == 2400
    # read k*w int32 from the delta, write them into the extent
    k, w = 37, 4
    assert scatter_append_roofline.append_bytes(k, w) == 2 * k * w * 4
    assert math.isclose(2400 / 819e9, 2.93040293040293e-09)
