"""From a profiler trace (``.xplane.pb``) to numbers.

What is read: on every device plane (``/device:TPU:<n>``) the line of XLA
operations — each event one operation's interval on the chip — and on the
host the benchmark's own annotations (names under ``chipbench.``), which
``run.py`` puts around its calls into the system.  Nothing in the program
names a step yet, so these annotations are the only host spans there are.

Busy is the union of the device-operation intervals inside the traced
window, averaged over the device planes; idle is the rest, attributed to the
innermost annotation that covers it.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

PREFIX = "chipbench."
WINDOW = PREFIX + "window"
STEP = PREFIX + "engine.step"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


class Covered:
    """Length of a union of intervals inside any [a, b), by prefix sums."""

    def __init__(self, intervals: Iterable[Interval]) -> None:
        self.merged = merge(intervals)
        self._starts = [s for s, _ in self.merged]
        self._ends = [e for _, e in self.merged]
        self._before = [0.0]
        for s, e in self.merged:
            self._before.append(self._before[-1] + (e - s))

    def _upto(self, t: float) -> float:
        i = bisect.bisect_right(self._starts, t)
        if i == 0:
            return 0.0
        return self._before[i - 1] + min(t, self._ends[i - 1]) - self._starts[i - 1]

    def within(self, a: float, b: float) -> float:
        return max(0.0, self._upto(b) - self._upto(a)) if b > a else 0.0


def self_times(events: Sequence[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Seconds by name, counting each instant once: an operation that
    holds others (a ``while`` around its body) keeps only what its
    children do not cover."""
    total: Dict[str, float] = {}
    stack: List[List] = []  # [end, name, start, covered_by_children]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, start, covered = stack.pop()
            total[name] = total.get(name, 0.0) + (end - start) - covered
            if stack:
                stack[-1][3] += end - start

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        if stack and end > stack[-1][0]:
            end = stack[-1][0]  # clip an overhang to its parent
        stack.append([end, name, start, 0.0])
    close(float("inf"))
    return total


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over the device planes
    devices: int
    device_ops: List[List] = field(default_factory=list)  # [name, seconds]
    idle_gaps: List[List] = field(default_factory=list)  # [annotation, seconds]
    # the benchmark's engine.step annotations: index -> device-busy seconds
    step_busy_s: Dict[int, float] = field(default_factory=dict)
    step_wall_s: Dict[int, float] = field(default_factory=dict)


def short_name(hlo: str) -> str:
    """An operation's kind and its result's shape, from the whole HLO
    instruction the profiler gives: ``%copy.139 = bf16[2,65,2,16,16]{...}
    copy(...)`` -> ``copy bf16[2,65,2,16,16]``.  The number XLA appends is
    dropped, so that the same operation of every layer adds up."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if not rest.startswith("(") \
        else "(tuple)"
    stem = head.lstrip("%").rstrip("0123456789").rstrip(".")
    return f"{stem} {shape}"[:80]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def reduce_trace(path: str, top: int = 10) -> TraceSummary:
    data = _load(path)
    device_ops: List[List[Tuple[float, float, str]]] = []
    host_lines: List[List[Tuple[float, float, str, Optional[int]]]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.append([
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9,
                         short_name(e.name))
                        for e in line.events])
        else:
            for line in plane.lines:
                ours = []
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        index = dict(e.stats).get("i")
                        ours.append((e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9,
                                     e.name, index))
                if ours:
                    host_lines.append(ours)
    if not device_ops:
        raise ValueError(f"{path}: no '{OPS_LINE}' line on any "
                         f"'{DEVICE_PLANE}<n>' plane")
    main = [line for line in host_lines
            if any(name == WINDOW for _, _, name, _ in line)]
    if len(main) != 1:
        raise ValueError(f"{path}: want one host thread with a '{WINDOW}' "
                         f"annotation, found {len(main)}")
    spans = main[0]
    w0, w1 = next((s, e) for s, e, name, _ in spans if name == WINDOW)
    covered = [Covered((max(s, w0), min(e, w1)) for s, e, _ in ops
                       if e > w0 and s < w1) for ops in device_ops]
    busy = sum(c.within(w0, w1) for c in covered) / len(covered)

    # operations by name, each instant counted once per device
    by_name: Dict[str, float] = {}
    for ops in device_ops:
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in ops
                  if e > w0 and s < w1]
        for name, seconds in self_times(inside).items():
            by_name[name] = by_name.get(name, 0.0) + seconds / len(device_ops)
    ops_top = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]

    # idle time by the innermost annotation that covers it (device 0's
    # timeline: with one chip there is no other)
    first = covered[0]
    idle: Dict[str, float] = {}
    marks = []
    for s, e, name, _ in spans:
        if name == WINDOW:
            continue
        s, e = max(s, w0), min(e, w1)
        if e > s:
            marks.append((s, 1, e, name))
    marks.sort(key=lambda m: (m[0], -m[2]))
    stack: List[Tuple[float, str]] = []  # (end, name)
    cursor = w0

    def account(upto: float) -> None:
        nonlocal cursor
        if upto > cursor:
            name = stack[-1][1] if stack else "unannotated"
            gap = (upto - cursor) - first.within(cursor, upto)
            idle[name] = idle.get(name, 0.0) + gap
            cursor = upto

    for s, _, e, name in marks:
        while stack and stack[-1][0] <= s:
            account(stack[-1][0])
            stack.pop()
        account(s)
        stack.append((e, name))
    while stack:
        account(stack[-1][0])
        stack.pop()
    account(w1)
    idle_top = sorted(((n[len(PREFIX):] if n.startswith(PREFIX) else n, s)
                       for n, s in idle.items()), key=lambda kv: -kv[1])[:top]

    summary = TraceSummary(
        window_s=w1 - w0, busy_s=busy, devices=len(device_ops),
        device_ops=[[n, s] for n, s in ops_top],
        idle_gaps=[[n, s] for n, s in idle_top])
    for s, e, name, index in spans:
        if name == STEP and index is not None and s >= w0 and e <= w1:
            summary.step_busy_s[int(index)] = first.within(s, e)
            summary.step_wall_s[int(index)] = e - s
    return summary
