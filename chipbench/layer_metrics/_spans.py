"""What the readers of the PROGRAM's own spans share (``_readers.py`` is for
those that time the program from outside).

``kubeshare_tpu/utils/profiling.py`` puts a span at every layer boundary of
the serving path (``kubeshare.engine.*``, ``kubeshare.guard.*``,
``kubeshare.client.acquire``) and, while the profiler runs, each is a host
event of the run's ``.xplane.pb`` on the device trace's clock.  ``load``
takes from that file, once a run: those events of every thread, the
benchmark's ``chipbench.window`` interval, and from the first device plane
the ``XLA Ops`` line (busy time) and the ``XLA Modules`` line (whose
program ran: the engine's are ``jit_kubeshare_<kind>_step``).  Everything is
read over the traced tail of the window, like ``step.*``; the three
``serving.ttft_tail_*`` metrics read ``RequestResult``'s stamps instead,
over the requests whose first token came inside the window.

A program without the spans (the parent of the PR that brought them) gives
every reader here nothing to read: each returns None and never raises.
"""

from __future__ import annotations

import functools
import json
import math
import os
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from chipbench import metrics
from chipbench.trace import (DEVICE_PLANE, OPS_LINE, WINDOW, Covered, _load,
                             find_xplane, merge)

TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".state", "trace")
PREFIX = "kubeshare."
MODULES_LINE = "XLA Modules"
ENGINE_MODULE = "jit_kubeshare_"
SCHEDULE = ("engine.admit", "engine.tune", "engine.plan")
# a launch outside any plan is a single-block pool write, not a dispatch
UNPLANNED = ("upload", "copy")


class Span(NamedTuple):
    start: float  # seconds on the trace's clock
    end: float
    thread: str
    attrs: Dict


@dataclass
class Spans:
    window: Tuple[float, float]
    host: Dict[str, List[Span]]  # by name less PREFIX
    busy: Covered  # the first device's XLA Ops
    modules: List[Tuple[float, float, str]]  # its XLA Modules

    def inside(self, name: str) -> List[Span]:
        """The spans called ``name`` that lie whole inside the window."""
        w0, w1 = self.window
        return [s for s in self.host.get(name, ())
                if s.start >= w0 and s.end <= w1]

    def launches(self) -> List[Span]:
        return [s for s in self.inside("engine.launch")
                if s.attrs.get("kind") not in UNPLANNED]


@functools.lru_cache(maxsize=2)
def load(path: str) -> Optional[Spans]:
    """The program's spans of one trace file; None where the file has no
    ``chipbench.window``."""
    window, host, ops, modules, device = None, {}, [], [], None
    for plane in _load(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            if device is not None:
                continue  # device 0's timeline, as trace.py attributes idle
            device = plane.name
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9, e.name)
                               for e in line.events]
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name == WINDOW:
                    window = (e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                elif name.startswith(PREFIX):
                    host.setdefault(name[len(PREFIX):], []).append(Span(
                        e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9,
                        line.name, dict(e.stats)))
    if window is None:
        return None
    return Spans(window, host, Covered(ops), modules)


def of(run: Dict) -> Optional[Spans]:
    """The spans of this run's trace; None for a run that was not traced
    or whose program has no spans."""
    if run.get("trace") is None:
        return None
    try:
        spans = load(find_xplane(TRACE_DIR))
    except FileNotFoundError:
        return None
    if spans is None or not spans.host:
        return None
    _say_once(spans, run)
    return spans


_said: set = set()


def _say_once(spans: Spans, run: Dict) -> None:
    """One earlier line a run: seconds and count of every span inside the
    traced tail, so that PERF.md's breakdown can be written from a run's
    output alone."""
    if id(spans) in _said:
        return
    _said.add(id(spans))
    w0, w1 = spans.window
    print(json.dumps({"program_spans": {
        name: [len(inside), round(sum(s.end - s.start for s in inside), 6)]
        for name in sorted(spans.host)
        for inside in [spans.inside(name)]},
        "traced_s": round(w1 - w0, 6),
        "device_busy_s": round(spans.busy.within(w0, w1), 6),
        "launches": len(spans.launches())}), flush=True)


def _is_pod(span: Span, name: str) -> bool:
    """The guard's ``pod`` is the token client's ``namespace/name``."""
    pod = str(span.attrs.get("pod", ""))
    return pod == name or pod.endswith("/" + name)


def ms_per_dispatch(run: Dict, names: Sequence[str]) -> Optional[float]:
    """Seconds of the spans called ``names`` over the planned launches of
    the traced tail, in milliseconds."""
    spans = of(run)
    if spans is None or not spans.launches():
        return None
    seconds = sum(s.end - s.start for n in names for s in spans.inside(n))
    return seconds / len(spans.launches()) * 1e3


def gated_idle_ms(run: Dict) -> Optional[float]:
    """Per ``guard.gated`` span of pod A — the interval tokend charges A
    for — its length less the device-busy time inside it: the launch and
    completion latency A pays tokens for.  Mean, in milliseconds."""
    spans = of(run)
    if spans is None:
        return None
    gated = [s for s in spans.inside("guard.gated") if _is_pod(s, run["pod_a"])]
    if not gated:
        return None
    idle = sum((s.end - s.start) - spans.busy.within(s.start, s.end)
               for s in gated)
    return idle / len(gated) * 1e3


def broker_wait_ms(run: Dict) -> Optional[float]:
    """Mean length of pod A's ``guard.acquire`` spans that went to the
    token client (``broker=1``), in milliseconds."""
    spans = of(run)
    if spans is None:
        return None
    asked = [s for s in spans.inside("guard.acquire")
             if _is_pod(s, run["pod_a"]) and int(s.attrs.get("broker", 0))]
    if not asked:
        return None
    return sum(s.end - s.start for s in asked) / len(asked) * 1e3


def cotenant_wall_over_device(run: Dict) -> Optional[float]:
    """What the co-tenant is charged over what the device gave it: the
    seconds of the ``guard.gated`` spans of every pod but A, over the device
    time of the programs that are not the engine's and ran inside them."""
    spans = of(run)
    if spans is None:
        return None
    gated = [s for s in spans.inside("guard.gated")
             if not _is_pod(s, run["pod_a"])]
    if not gated:
        return None
    theirs = Covered((s, e) for s, e, name in spans.modules
                     if not name.startswith(ENGINE_MODULE))
    device = sum(theirs.within(a, b)
                 for a, b in merge((s.start, s.end) for s in gated))
    if device <= 0:
        return None
    return sum(s.end - s.start for s in gated) / device


def ttft_tail_parts(run: Dict) -> Optional[Dict[str, float]]:
    """The slowest tenth by time to first token (``chipbench.metrics``'
    rule) of the requests whose first token came inside the window: the
    mean of each part of that time, by the stamps the program puts on
    ``RequestResult``, in milliseconds."""
    record = run["record"]
    if record["backlog"]:
        return None
    closes = record["opened_at"] + record["seconds"]
    timed = []
    for entry in record["sent"].values():
        result = entry["result"]
        if not entry["scored"] or result.first_token_at is None \
                or result.first_token_at > closes:
            continue
        if getattr(result, "first_dispatch_at", None) is None \
                or result.admitted_at is None:
            return None  # a program without the stamp
        timed.append((metrics.ttft_seconds(
            record["opened_at"], entry["request"].due,
            result.first_token_at, record["seconds"]), entry))
    if not timed:
        return None
    k = max(1, math.ceil(len(timed) / 10))
    tail = sorted(timed, key=lambda pair: pair[0])[-k:]
    results = [entry["result"] for _, entry in tail]

    def mean_ms(values) -> float:
        return sum(values) / k * 1e3

    parts = {
        "ttft": mean_ms(t for t, _ in tail),
        "late": mean_ms(r.submitted_at - (record["opened_at"]
                                          + entry["request"].due)
                        for r, (_, entry) in zip(results, tail)),
        "queue": mean_ms(r.admitted_at - r.submitted_at for r in results),
        "prefill_wait": mean_ms(r.first_dispatch_at - r.admitted_at
                                for r in results),
        "prefill": mean_ms(r.first_token_at - r.first_dispatch_at
                           for r in results),
        "prefill_chunks": sum(r.prefill_chunks for r in results) / k,
    }
    if id(record) not in _said:
        _said.add(id(record))
        print(json.dumps({"ttft_tail_parts_ms": parts, "requests": k}),
              flush=True)
    return parts
