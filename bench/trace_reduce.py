"""Reduce a profiler trace (``.xplane.pb``) to what the benchmark reports.

The window is the host span named ``WINDOW_SPAN`` that the harness opens and
closes on its main thread. Within it, for each device plane:

* busy time: the union of the intervals of the operations on its ops line;
* the operations that took most device time, counting only operations that
  hold no other (a ``while`` loop's body ops count, the loop itself not),
  each named by its HLO name and output type;
* the idle gaps, each attributed to the innermost host span on the main
  thread that covers the middle of the gap ("no host span" where none does).

Only ``jax.profiler.ProfileData`` is needed to read the file.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench_window"
DEVICE_PREFIX = "/device:"
OPS_LINES = ("XLA Ops",)
NO_SPAN = "no host span"

Interval = Tuple[float, float]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # mean over the device planes read
    devices: int
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_trace(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of sorted, disjoint ``busy`` within [lo, hi]."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: Sequence[Tuple[float, float, str]], times: Sequence[float]) -> List[str]:
    """For each of the ascending ``times``, the name of the shortest span
    that covers it: one sweep, with a heap of the spans begun so far."""
    order = sorted(spans)
    heap: List[Tuple[float, float, str]] = []
    out, i = [], 0
    for t in times:
        while i < len(order) and order[i][0] <= t:
            a, b, name = order[i]
            heapq.heappush(heap, (b - a, b, name))
            i += 1
        while heap and heap[0][1] < t:      # ended before t, and so before every later t
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else NO_SPAN)
    return out


def short_name(hlo: str) -> str:
    """``%fusion.4 = f32[8,128]{1,0:T(8,128)} fusion(...)`` -> ``%fusion.4 = f32[8,128]``."""
    name, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo[:120]
    rest = re.sub(r"\{[^}]*\}", "", rest)             # layouts
    out_type = rest[:rest.find(")") + 1] if rest.startswith("(") else rest.split(" ")[0]
    return f"{name} = {out_type}"[:120]


def leaves(events: Sequence[Tuple[str, float, float]]) -> List[Tuple[str, float, float]]:
    """The events that contain no other event of the same line."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(evs, evs[1:] + [None]) if nxt is None or nxt[1] >= e[2]]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns)


def reduce_planes(planes, top: int = 10) -> TraceSummary:
    """``planes``: objects with ``name`` and ``lines``; each line has
    ``name`` and ``events`` with ``name``, ``start_ns``, ``duration_ns``."""
    window: Optional[Interval] = None
    host_spans: List[Tuple[float, float, str]] = []
    device_lines = []
    for plane in planes:
        lines = list(plane.lines)
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [ln for ln in lines if ln.name in OPS_LINES]
            if ops:
                device_lines.append(ops[0])
            continue
        for line in lines:
            evs = list(_events(line))
            win = [(a, b) for name, a, b in evs if name == WINDOW_SPAN]
            if win and window is None:
                window = win[0]
                host_spans = [(a, b, n) for n, a, b in evs if n != WINDOW_SPAN]
    if window is None:
        raise ValueError(f"trace has no host span named {WINDOW_SPAN!r}")
    if not device_lines:
        seen = [(p.name, [ln.name for ln in p.lines]) for p in planes]
        raise ValueError(f"trace has no device ops line among {seen}")
    lo, hi = window
    busy_total = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    for line in device_lines:
        evs = list(_events(line))
        for name, a, b in leaves(evs):
            for c in clip([(a, b)], lo, hi):
                op_time[short_name(name)] += c[1] - c[0]
        busy = union(clip([(a, b) for _, a, b in evs], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        idle = gaps(busy, lo, hi)
        for (a, b), name in zip(idle, innermost(host_spans, [(a + b) / 2 for a, b in idle])):
            gap_time[name] += b - a
    n = len(device_lines)
    order = lambda d: sorted(((k, v / n * 1e-9) for k, v in d.items()),  # noqa: E731
                             key=lambda kv: -kv[1])[:top]
    return TraceSummary(window_s=(hi - lo) * 1e-9, busy_s=busy_total / n * 1e-9,
                        devices=n, device_ops=order(op_time), idle_gaps=order(gap_time))


def reduce_file(path: str, top: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, top=top)
