"""The one traffic generator: a mix file's parameters -> a timed schedule.

A mix (`bench/traffic/<name>.json`) states the share of reads among
operations, how many update operations make one submitted batch, the
share of enrolments among update operations, the warm-up, and how the
driver batches reads.  The cell (`bench/cells/<cell>.json`) states the
offered rate in operations per second.  The configuration's generator
module supplies the update operations themselves.

Arrivals are an open loop, Poisson in shape: the gaps between events
are the quantiles of an exponential distribution in a shuffled order.
That order, which events are reads and which update batches, which
template each read asks for, and the kinds and sizes of the update
operations come from one fixed stream, the same for every seed: the
queueing a run sees is then the same from seed to seed, where a
shuffle per seed moved the median read latency by 88% (IQR over
median, six seeds, one v5e).  The seed draws the store, which
department, courses and advisor an enrolment gets, and which student a
withdrawal takes.  The schedule is a pure function of (mix, rate,
seconds, seed, store).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class Read:
    due: float
    name: str
    index: int                 # position among the schedule's reads


@dataclass
class UpdateBatch:
    due: float
    inserts: np.ndarray
    deletes: np.ndarray
    index: int                 # position among the schedule's batches


@dataclass
class Schedule:
    events: list               # Read | UpdateBatch, by due time
    reads: list[Read]
    batches: list[UpdateBatch]
    warmup: list[tuple[np.ndarray, np.ndarray]] = field(default_factory=list)


def counts(mix: dict, rate: float, seconds: float) -> tuple[int, int, int]:
    """(reads, update batches, update operations) offered in a window."""
    ops = rate * seconds
    n_reads = int(round(ops * mix["read_share"]))
    n_batches = int(round(ops * (1.0 - mix["read_share"])
                          / mix["update_batch_ops"]))
    return n_reads, n_batches, n_batches * int(mix["update_batch_ops"])


def enrolment_ids_needed(mix: dict, rate: float, seconds: float) -> int:
    """Students the window's enrolments and the warm-up may enrol."""
    _, _, n_ops = counts(mix, rate, seconds)
    return n_ops + int(mix["warmup_ops"]) + 1


def template_counts(weights: dict[str, float], n: int) -> list[str]:
    """`n` template names in proportion to `weights` (largest remainder,
    ties to the earlier name)."""
    names = list(weights)
    w = np.asarray([weights[k] for k in names], float)
    exact = n * w / w.sum()
    base = np.floor(exact).astype(int)
    rest = np.argsort(-(exact - base), kind="stable")[: n - base.sum()]
    base[rest] += 1
    return [k for k, c in zip(names, base) for _ in range(c)]


def _arrivals(rng, n: int, seconds: float) -> np.ndarray:
    """`n` arrival times in (0, seconds): exponential-quantile gaps,
    shuffled, scaled so that one mean gap is left after the last."""
    if n == 0:
        return np.zeros(0)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    return np.cumsum(gaps) * (seconds / (gaps.sum() + gaps.mean()))


def _net(ops) -> tuple[np.ndarray, np.ndarray]:
    """One batch from several operations, in order: a triple inserted
    and then deleted within the batch (or the reverse) nets out."""
    ins: dict[tuple, None] = {}
    dels: dict[tuple, None] = {}
    for oi, od in ops:
        for t in map(tuple, oi.tolist()):
            if t in dels:
                del dels[t]
            else:
                ins[t] = None
        for t in map(tuple, od.tolist()):
            if t in ins:
                del ins[t]
            else:
                dels[t] = None

    def arr(d):
        return np.asarray(list(d), np.int32).reshape(-1, 3)

    return arr(ins), arr(dels)


def build(mix: dict, rate: float, seconds: float, seed: int, universe,
          gen, weights: dict[str, float]) -> Schedule:
    """The window's schedule; `gen` is the configuration's generator
    module (its `UpdateSource` and `warmup_batches`)."""
    shape = np.random.default_rng([0, 1])
    rng = np.random.default_rng([seed, 1])
    n_reads, n_batches, n_ops = counts(mix, rate, seconds)
    kinds = shape.permutation(np.r_[np.zeros(n_reads, bool),
                                    np.ones(n_batches, bool)])
    times = _arrivals(shape, len(kinds), seconds)
    names = list(shape.permutation(template_counts(weights, n_reads)))
    src = gen.UpdateSource(universe, rng, n_ops, mix["enrol_share"],
                           order=shape)
    warm = gen.warmup_batches(universe, rng, int(mix["warmup_ops"]))
    per = int(mix["update_batch_ops"])
    events, reads, batches = [], [], []
    for due, is_update in zip(times.tolist(), kinds.tolist()):
        if is_update:
            ins, dels = _net([src.op() for _ in range(per)])
            ev = UpdateBatch(due, ins, dels, len(batches))
            batches.append(ev)
        else:
            ev = Read(due, str(names[len(reads)]), len(reads))
            reads.append(ev)
        events.append(ev)
    return Schedule(events, reads, batches, warm)
