"""What the readers of device time BY STAGE share: the ``step.stage_ms.*``,
``step.stage_unscoped_share.*``, ``step.experts_hbm_roofline.backlog`` and
``step.attend_kernel_hbm_roofline.*`` metrics.

The program says which of a step program's instructions belong to which stage
(``kubeshare_tpu/serving/stages.py``: ``stage_table(program)`` gives
{instruction name: stage}, from the ``jax.named_scope``s its stages are
written under) and names the program on every ``kubeshare.engine.launch``
span (``program``).  The trace says when each instruction ran: the first
device plane's ``XLA Ops`` events carry the instruction's name, the ``XLA
Modules`` line the program.  ``book`` joins the two over the traced tail of
the window: each planned launch gets the engine's module intervals
(``jit_kubeshare_<kind>_step``) whose middle lies between its start and the
end of the ``kubeshare.engine.device_wait`` that follows it on its thread (the
middle: the two clocks differ by part of a millisecond), and every
operation inside them is booked to ``stage_table(launch.program)[name]`` by
``trace.self_times``' rule (a ``while`` keeps only what its body does not
cover).  Launch by launch, so that two programs whose instruction names
collide are each read by their own table.

A program without the table or without ``program`` on its launches (the
parent of the PR that brought them), a launch whose program no table is
known for, or a run without a trace gives every reader here nothing to read:
each returns None and never raises.  The tables are built here, after the
window (one lowering a program, the executable from the compile cache the
warm-up filled), only for the programs the tail's launches name.
"""

from __future__ import annotations

import bisect
import functools
import json
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from chipbench.layer_metrics import _readers, _routing, _spans
from chipbench.trace import (DEVICE_PLANE, OPS_LINE, _load, find_xplane,
                             self_times)

UNSCOPED = "unscoped"
KERNEL = "paged_"  # the paged attention kernels' instructions
DIFFUSION_KINDS = ("diffusion", "mixed_diffusion")  # one kernel pass each

Op = Tuple[float, float, str]  # start, end, instruction name


@dataclass
class Launch:
    span: _spans.Span
    stages: Dict[str, float] = field(default_factory=dict)  # seconds
    kernel_s: float = 0.0  # of the `attention` operations named paged_*
    busy_s: float = 0.0  # device-busy seconds inside its module intervals


@dataclass
class Booked:
    launches: List[Launch]
    table_build_s: float


def instruction_name(event: str) -> str:
    """``%fusion.12 = bf16[128,768]{1,0} fusion(...)`` -> ``fusion.12``: the
    profiler gives the whole instruction; its name is what the table has."""
    head, sep, _ = event.partition(" = ")
    return (head if sep else event).strip().lstrip("%")


@functools.lru_cache(maxsize=2)
def load_ops(path: str) -> List[Op]:
    """The first device plane's ``XLA Ops`` events WITH their instruction
    names, by start (device 0's timeline, as ``_spans.load`` takes it)."""
    for plane in _load(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    return sorted(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9,
                         instruction_name(e.name)) for e in line.events)
            return []
    return []


def _program_tables() -> Optional[Callable[[str], Optional[Dict[str, str]]]]:
    try:
        from kubeshare_tpu.serving import stages
    except ImportError:
        return None  # a program from before the table
    return stages.stage_table


def book(spans: _spans.Spans, ops: Sequence[Op],
         table_of: Callable[[str], Optional[Dict[str, str]]]
         ) -> Optional[Booked]:
    """Device seconds by stage of every planned launch of the traced tail
    that a ``device_wait`` follows; None where a launch names no program or
    a program no table is known for."""
    launches = spans.launches()
    if not launches or any("program" not in s.attrs for s in launches):
        return None
    tables: Dict[str, Optional[Dict[str, str]]] = {}
    t0 = time.monotonic()
    for name in sorted({s.attrs["program"] for s in launches}):
        tables[name] = table_of(name)
        if tables[name] is None:
            return None
    build_s = time.monotonic() - t0
    waits: Dict[str, Tuple[List[float], List[float]]] = {}  # starts, ends
    for wait in sorted(spans.inside("engine.device_wait"),
                       key=lambda w: w.start):
        starts, ends = waits.setdefault(wait.thread, ([], []))
        starts.append(wait.start)
        ends.append(wait.end)
    # by the middle of each: the device's clock and the host's differ (a
    # module began 0.5 ms BEFORE its launch span in one trace, PR 38)
    modules = sorted((m for m in spans.modules
                      if m[2].startswith(_spans.ENGINE_MODULE)),
                     key=lambda m: m[0] + m[1])
    middles = [(m[0] + m[1]) / 2 for m in modules]
    op_starts = [o[0] for o in ops]
    booked = []
    for span in launches:
        starts, ends = waits.get(span.thread, ([], []))
        i = bisect.bisect_left(starts, span.end)
        if i == len(starts):
            continue  # the window closed on it; an unguarded engine
        table = tables[span.attrs["program"]]
        module = f"{_spans.ENGINE_MODULE}{span.attrs['kind']}_step"
        launch = Launch(span)
        lo = bisect.bisect_left(middles, span.start)
        hi = bisect.bisect_right(middles, ends[i])
        for m0, m1, ran in modules[lo:hi]:
            if not ran.startswith(module):
                continue
            launch.busy_s += spans.busy.within(m0, m1)
            inside = ops[bisect.bisect_left(op_starts, m0):
                         bisect.bisect_left(op_starts, m1)]
            for name, seconds in self_times(inside).items():
                stage = table.get(name, UNSCOPED)
                launch.stages[stage] = launch.stages.get(stage, 0.0) + seconds
                if stage == "attention" and name.startswith(KERNEL):
                    launch.kernel_s += seconds
        booked.append(launch)
    return Booked(booked, build_s) if booked else None


_booked: Dict[int, Optional[Booked]] = {}  # by id of the run's Spans


def of(run: Dict) -> Optional[Booked]:
    """This run's launches, booked once a run; one earlier line says what
    was found."""
    spans = _spans.of(run)
    table_of = _program_tables()
    if spans is None or table_of is None:
        return None
    if id(spans) not in _booked:
        booked = book(spans, load_ops(find_xplane(_spans.TRACE_DIR)),
                      table_of)
        _booked.clear()  # one run a process: keep the newest alone
        _booked[id(spans)] = booked
        if booked is not None:
            _say(booked, spans)
    return _booked[id(spans)]


def _say(booked: Booked, spans: _spans.Spans) -> None:
    """``PERF.md``'s breakdown by stage is written from this line."""
    by_kind: Dict[str, Dict[str, float]] = {}
    count: Dict[str, int] = {}
    for launch in booked.launches:
        kind = launch.span.attrs["kind"]
        count[kind] = count.get(kind, 0) + 1
        stages = by_kind.setdefault(kind, {})
        for stage, seconds in launch.stages.items():
            stages[stage] = stages.get(stage, 0.0) + seconds
    admits = spans.inside("engine.admit")
    print(json.dumps({
        "program_stages": {kind: {s: round(v, 6) for s, v in
                                  sorted(stages.items())}
                           for kind, stages in sorted(by_kind.items())},
        "launches": count,
        "table_build_s": round(booked.table_build_s, 3),
        "programs": sorted({l.span.attrs["program"]
                            for l in booked.launches}),
        "module_busy_s": round(sum(l.busy_s for l in booked.launches), 6),
        "kernel_s": round(sum(l.kernel_s for l in booked.launches), 6),
        # what the admit phase saw, beside engine.schedule_ms_per_dispatch
        "admit": {"calls": len(admits), **{
            name: sum(int(s.attrs.get(name, 0)) for s in admits)
            for name in ("queued", "admitted", "matched_rows")}},
    }), flush=True)


def stage_ms(run: Dict, stage: str) -> Optional[float]:
    """Device milliseconds a planned launch spent in ``stage``."""
    booked = of(run)
    if booked is None:
        return None
    seconds = sum(l.stages.get(stage, 0.0) for l in booked.launches)
    return seconds / len(booked.launches) * 1e3 if seconds > 0 else None


def unscoped_share(run: Dict) -> Optional[float]:
    """``unscoped`` seconds over the engine modules' device-busy seconds,
    in percent: how far the table can be trusted."""
    booked = of(run)
    if booked is None:
        return None
    busy = sum(l.busy_s for l in booked.launches)
    if busy <= 0:
        return None
    return sum(l.stages.get(UNSCOPED, 0.0)
               for l in booked.launches) / busy * 100.0


def experts_hbm_roofline(run: Dict) -> Optional[float]:
    """The least time HBM could take for the routed experts over stage
    ``experts``' seconds, in percent.  Least bytes: every (pass, layer,
    expert) the routing touched reads that expert's three matrices once —
    ``touched`` of the tail's ``kubeshare.engine.routing`` spans (made when
    the next step consumes a dispatch: scaled to the launches booked) x the
    configuration's ``expert_bytes``.  A tile's rows are far under the 240
    FLOP a byte at which the v5e turns, so bytes bound."""
    booked = of(run)
    counts = _routing.totals(run) if booked is not None else None
    if counts is None or not hasattr(run["roofline"], "expert_bytes"):
        return None
    roof = run["roofline"]
    seconds = sum(l.stages.get("experts", 0.0) for l in booked.launches)
    if seconds <= 0:
        return None
    touched = counts["touched"] * len(booked.launches) / counts["spans"]
    peak = _readers.roofline.peaks(run["device_kind"])["hbm_bytes_per_s"]
    return touched * roof.expert_bytes(run["tc"]) / peak / seconds * 100.0


def attend_kernel_hbm_roofline(run: Dict) -> Optional[float]:
    """The least time HBM could take for what the paged kernels' calls had
    to read over the seconds of the ``attention`` operations named
    ``paged_*``, in percent, over the launches whose lanes ran a kernel
    (``attend`` = ``kernel``).  Least bytes: the rows the lanes held at
    launch (``rows``; the ``kv_rows`` of a diffusion pass's span count the
    same lanes' same rows) x what a step reads of a cached row in every
    layer x the dispatch's kernel passes (``decode_span``; 1 for a
    diffusion pass).  The rows a span's steps add, the block's own rows and
    a page's unread tail are left out."""
    booked = of(run)
    if booked is None:
        return None
    ran = [l for l in booked.launches if l.span.attrs.get("attend")
           == "kernel" and int(l.span.attrs.get("lanes", 0)) > 0]
    seconds = sum(l.kernel_s for l in ran)
    if seconds <= 0:
        return None
    roof = run["roofline"]
    row_bytes = getattr(roof, "kv_read_bytes_per_row",
                        roof.kv_bytes_per_row)(run["tc"])
    span = run["record"]["decode_span"]
    rows = sum(int(l.span.attrs["rows"])
               * (1 if l.span.attrs["kind"] in DIFFUSION_KINDS else span)
               for l in ran)
    peak = _readers.roofline.peaks(run["device_kind"])["hbm_bytes_per_s"]
    return rows * row_bytes / peak / seconds * 100.0
