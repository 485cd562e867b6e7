"""Reduce a profiler trace of the measured window to the device numbers.

One reduction for every device metric, so two PRs compute them alike:

* each device plane's ``XLA Modules`` line is read, and only that line,
  for time: one event per program execution, never nested, so nothing is
  counted twice.  Busy time is the union of those intervals inside the
  window, averaged over the devices used; a program's device time is the
  sum of its events' durations (name without the ``(fingerprint)``
  suffix, e.g. ``jit_chain``);
* the ``XLA Ops`` line is read only for the breakdown, as each op's self
  time (its duration less that of the ops nested in it);
* idle gaps (the window less busy time) are charged to the innermost
  host span open over each part of them, or to ``no span open``.

Times are in seconds.  Host spans are placed on the trace's clock by the
``bench.window`` annotation the harness opens around the window.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_MARK = "bench.window"
NO_SPAN = "no span open"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the given intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def complement(busy: Sequence[Interval], lo: float,
               hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no (sorted, disjoint) interval covers."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def module_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].strip()


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Per name, the time events on one line spend outside the events
    nested in them (nesting is time containment)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []           # [name, end, child time]
    for name, t0, t1 in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= t0:
            n, _, kids = stack.pop()
            out[n] -= kids
        if stack:
            stack[-1][2] += min(t1, stack[-1][1]) - t0
        out[name] += t1 - t0
        stack.append([name, t1, 0.0])
    for n, _, kids in stack:
        out[n] -= kids
    return dict(out)


def charge_gaps(gaps: Sequence[Interval],
                segments: Sequence[Tuple[float, float, str]]
                ) -> Dict[str, float]:
    """Split each gap over the (sorted, disjoint) named segments that
    cover it; what no segment covers goes to :data:`NO_SPAN`."""
    starts = [s[0] for s in segments]
    out: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(segments) and segments[i][0] < b:
            s0, s1, name = segments[i]
            ov = min(b, s1) - max(a, s0)
            if ov > 0:
                out[name] += ov
                covered += ov
            i += 1
        if b - a - covered > 0:
            out[NO_SPAN] += b - a - covered
    return dict(out)


def self_segments(spans: Sequence[Tuple[str, float, float]]
                  ) -> List[Tuple[float, float, str]]:
    """Where each span is the innermost one open, from the (name, start,
    end) spans of one properly nested span tree."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []          # (name, end)
    cursor = 0.0
    for name, t0, t1 in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= t0:
            n, end = stack.pop()
            segs.append((cursor, end, n))
            cursor = max(cursor, end)
        if stack:
            segs.append((cursor, t0, stack[-1][0]))
        cursor = t0
        stack.append((name, min(t1, stack[-1][1]) if stack else t1))
    while stack:
        n, end = stack.pop()
        segs.append((cursor, end, n))
        cursor = max(cursor, end)
    return [s for s in segs if s[1] > s[0]]


@dataclass
class DeviceWindow:
    """What the trace says about the measured window."""

    window_s: float
    busy_s: float                                  # mean over devices
    devices: int
    module_s: Dict[str, float] = field(default_factory=dict)
    op_self_s: Dict[str, float] = field(default_factory=dict)
    gaps: List[Interval] = field(default_factory=list)   # first device

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def device_planes(profile) -> list:
    return [p for p in profile.planes
            if re.match(r"/device:(TPU|GPU):\d+$", p.name)]


def window_mark(profile) -> Optional[Interval]:
    """(start, end) in seconds of the harness's window annotation."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_MARK:
                    return (ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
    return None


def reduce_devices(profile, lo: float, hi: float) -> DeviceWindow:
    """Busy time, program times, op self times and idle gaps in [lo, hi]
    (seconds on the trace's clock)."""
    planes = device_planes(profile)
    if not planes:
        raise ValueError("the trace has no device plane")
    busy_total = 0.0
    module_s: Dict[str, float] = defaultdict(float)
    op_s: Dict[str, float] = defaultdict(float)
    first_gaps: List[Interval] = []
    for k, plane in enumerate(planes):
        lines = {line.name: line for line in plane.lines}
        modules = []
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                a = ev.start_ns * 1e-9
                b = a + ev.duration_ns * 1e-9
                if b > lo and a < hi:
                    name = module_name(ev.name)
                    a, b = max(a, lo), min(b, hi)
                    modules.append((a, b, name))
                    module_s[name] += b - a
        busy = union((a, b) for a, b, _ in modules)
        busy_total += sum(b - a for a, b in busy)
        if k == 0:
            first_gaps = complement(busy, lo, hi)
            if OPS_LINE in lines:
                mods = sorted(modules)
                starts = [m[0] for m in mods]
                evs = []
                for ev in lines[OPS_LINE].events:
                    a = ev.start_ns * 1e-9
                    b = a + ev.duration_ns * 1e-9
                    if b <= lo or a >= hi:
                        continue
                    i = bisect.bisect_right(starts, a) - 1
                    prog = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
                    evs.append((f"{prog}/{op_name(ev.name)}",
                                max(a, lo), min(b, hi)))
                for name, t in self_times(evs).items():
                    op_s[name] += t
    return DeviceWindow(window_s=hi - lo, busy_s=busy_total / len(planes),
                        devices=len(planes), module_s=dict(module_s),
                        op_self_s=dict(op_s), gaps=first_gaps)


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
