"""Reduce a `jax.profiler` trace (`.xplane.pb`) to device busy time,
idle gaps and per-program device time.

Planes named `/device:TPU:<n>` are the chips.  Their `XLA Ops` line
holds one event per operation that ran; the union of those intervals is
the busy time.  Their `XLA Modules` line holds one event per compiled
program run, named `jit_<function>(<fingerprint>)`; the fingerprint is
dropped, so a kernel jitted as `scatter_append_pallas` is found as
`jit_scatter_append_pallas` after any change to its body.  Host spans
(`jax.profiler.TraceAnnotation`) sit on the `/host:CPU` plane, on the
same clock, and name what the host was doing during each idle gap.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclass
class Reduced:
    window: tuple[float, float]          # ns, the traced window
    busy_ns: list[float]                 # per device plane, in window
    idle_gaps: list[tuple[float, float]]  # of the first device, in window
    modules: dict[str, list[tuple[float, float]]] = field(
        default_factory=dict)            # program -> [(start, dur)] in window
    spans: list[tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices traced."""
        if not self.busy_ns:
            return 0.0
        return sum(self.busy_ns) / len(self.busy_ns) * 1e-9

    def module_seconds(self, prefix: str) -> float:
        return sum(d for name, runs in self.modules.items()
                   if name.startswith(prefix) for _, d in runs) * 1e-9

    def labels(self, gaps) -> list[str]:
        """For each gap, the innermost host span covering its middle.
        Host spans of one thread nest, so a sweep with a stack finds it."""
        marks = []
        for k, (name, s, e) in enumerate(self.spans):
            marks += [(s, 0, k), (e, 2, k)]
        marks += [((a + b) / 2, 1, j) for j, (a, b) in enumerate(gaps)]
        marks.sort()
        out = ["host outside any span"] * len(gaps)
        stack: list[int] = []
        for _t, kind, k in marks:
            if kind == 0:
                stack.append(k)
            elif kind == 2:
                if k in stack:
                    stack.remove(k)
            elif stack:
                out[k] = self.spans[stack[-1]][0]
        return out


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files in {log_dir}")
    return paths[0]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(path: str, window_span: str = "bench.window") -> Reduced:
    """Reduce one trace file.  The window is the host span named
    `window_span`; without one, the extent of the device events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: list[tuple[str, float, float]] = []
    devices: list[tuple[list, dict]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], {}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        mods.setdefault(FINGERPRINT.sub("", e.name), []).append(
                            (e.start_ns, e.duration_ns))
            devices.append((ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith("bench.")]
    win = [(s, e) for n, s, e in spans if n == window_span]
    if win:
        lo, hi = win[0]
    else:
        all_ops = [iv for ops, _ in devices for iv in ops]
        lo = min((s for s, _ in all_ops), default=0.0)
        hi = max((e for _, e in all_ops), default=0.0)
    busy, gaps, modules = [], [], {}
    for k, (ops, mods) in enumerate(devices):
        merged = _clip(_union(ops), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        if k == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
            modules = {name: [(s, d) for s, d in runs if lo <= s < hi]
                       for name, runs in mods.items()}
    return Reduced(window=(lo, hi), busy_ns=busy, idle_gaps=gaps,
                   modules=modules, spans=spans)


def breakdown(r: Reduced, top: int = 10) -> dict:
    """The programs that took most device time, and the idle time
    summed by what the host was doing, each at most `top` entries."""
    ops = sorted(((name, sum(d for _, d in runs) * 1e-9)
                  for name, runs in r.modules.items()),
                 key=lambda x: -x[1])[:top]
    idle: dict[str, float] = {}
    for (a, b), lab in zip(r.idle_gaps, r.labels(r.idle_gaps)):
        idle[lab] = idle.get(lab, 0.0) + (b - a) * 1e-9
    gaps = sorted(idle.items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
