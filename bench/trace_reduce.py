"""Reduction of a profiler trace (`.xplane.pb`) to device metrics.

- The window is the host span `bench.traced`, which the harness holds
  open from just after the trace starts to just before it stops.
- Busy time is the union of the intervals of the ops on a device's
  `XLA Ops` line, clipped to the window, averaged over the devices that
  ran anything; idle share is 1 - busy / window.
- An op's device time is the sum of its events' durations in the window,
  keyed by `<program>:<instruction>`, the program being the `XLA Modules`
  event that holds it; `module_time_s` sums the programs' own events by
  name, which is how the decode kernels are found.
- Each idle gap is named by the host span that covers most of it:
  `Pod.tick`, `Pod.submit`, or `<query>.host`, a query's own host work
  outside the scans it waits for.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW_SPAN = "bench.traced"
HOST_SPANS = ("Pod.tick", "Pod.submit")
QUERY_PREFIX = "query."
WAIT_SPAN = "scan.wait"

Interval = Tuple[int, int]


@dataclasses.dataclass
class Reduction:
    window: Interval  # ns, on the trace's clock
    n_devices: int
    busy_ns: float  # union of op intervals, averaged over devices
    ops: Dict[str, float]  # op name -> device ns
    modules: Dict[str, float]  # program name -> device ns
    gaps: List[Tuple[str, float]]  # (host span, ns), longest first

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def module_time_s(self, patterns: Sequence[str]) -> float:
        rx = [re.compile(p) for p in patterns]
        return sum(ns for name, ns in self.modules.items()
                   if any(r.search(name) for r in rx)) * 1e-9


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def gaps_of(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def subtract(intervals: List[Interval], holes: List[Interval]) -> List[Interval]:
    """Parts of `intervals` not covered by `holes` (both unions)."""
    out = []
    for a, b in intervals:
        t = a
        for c, d in clip(holes, a, b):
            if c > t:
                out.append((t, c))
            t = max(t, d)
        if b > t:
            out.append((t, b))
    return out


def overlap(intervals: List[Interval], lo: int, hi: int) -> int:
    """Length of `intervals` (a union) inside [lo, hi)."""
    starts = [a for a, _ in intervals]
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    total = 0
    for a, b in intervals[i:]:
        if a >= hi:
            break
        total += max(0, min(b, hi) - max(a, lo))
    return total


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns), e) for e in line.events]


def host_labels(host_lines) -> Dict[str, List[Interval]]:
    """Host span label -> union of its intervals.  A query's label covers
    its span minus the scans it waits for on the same thread."""
    spans: Dict[str, List[Interval]] = {}
    for line in host_lines:
        evs = _events(line)
        waits = union((a, b) for n, a, b, _ in evs if n == WAIT_SPAN)
        for n, a, b, _ in evs:
            if n in HOST_SPANS:
                spans.setdefault(n, []).append((a, b))
            elif n.startswith(QUERY_PREFIX):
                label = n[len(QUERY_PREFIX):] + ".host"
                spans.setdefault(label, []).extend(subtract([(a, b)], waits))
    return {k: union(v) for k, v in spans.items()}


def _module(name: str) -> str:
    """A program's name without the hash the trace appends to it."""
    return re.sub(r"\(\d+\)$", "", name)


def _op(name: str) -> str:
    """An op's HLO instruction name, without its shapes and operands."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce(planes, n_gaps: int = 10) -> Reduction:
    """Reduce the planes of a `jax.profiler.ProfileData`."""
    planes = list(planes)
    host = [p for p in planes if p.name.startswith("/host:")]
    host_lines = [ln for p in host for ln in p.lines]
    window = None
    for line in host_lines:
        for n, a, b, _ in _events(line):
            if n == WINDOW_SPAN:
                window = (a, b)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = window
    busy_total, n_dev = 0, 0
    ops: Dict[str, float] = {}
    modules: Dict[str, float] = {}
    all_gaps: List[Interval] = []
    for p in planes:
        if not p.name.startswith("/device:") or "CPU" in p.name:
            continue
        lines = {ln.name: ln for ln in p.lines}
        if "XLA Ops" not in lines:
            continue
        progs = sorted((a, b, _module(n)) for n, a, b, _ in
                       _events(lines["XLA Modules"])) if "XLA Modules" in lines else []
        starts = [a for a, _, _ in progs]
        for a, b, n in progs:
            if b > lo and a < hi:
                modules[n] = modules.get(n, 0.0) + min(b, hi) - max(a, lo)
        evs = [(n, max(a, lo), min(b, hi)) for n, a, b, _ in _events(lines["XLA Ops"])
               if b > lo and a < hi]
        if not evs:
            continue
        n_dev += 1
        busy = union((a, b) for _, a, b in evs)
        busy_total += sum(b - a for a, b in busy)
        for n, a, b in evs:
            i = bisect.bisect_right(starts, a) - 1
            prog = progs[i][2] if i >= 0 and progs[i][1] >= b else "?"
            key = f"{prog}:{_op(n)}"
            ops[key] = ops.get(key, 0.0) + (b - a)
        all_gaps.extend(gaps_of(busy, lo, hi))
    labels = host_labels(host_lines)
    named = []
    for a, b in sorted(all_gaps, key=lambda g: g[0] - g[1])[:n_gaps]:
        cover = {k: overlap(v, a, b) for k, v in labels.items()}
        best = max(cover, key=cover.get) if cover else None
        named.append((best if best and cover[best] > 0 else "no host span", float(b - a)))
    return Reduction(window, n_dev, busy_total / max(n_dev, 1), ops, modules, named)


def load(path: str) -> Reduction:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path).planes)
