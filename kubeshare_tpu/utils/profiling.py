"""The one span mechanism of the program, and the operator's trace switch.

``span(name, **attrs)`` times a block on ``time.monotonic()`` — the clock
``RequestResult``, the guard and tokend's charges already use — into one
process-wide bounded ring, and enters a ``jax.profiler.TraceAnnotation`` of
the same name, so that while a profiler session runs the span is in the
``.xplane.pb`` on the device trace's own clock.  There is no switch:
"tracing on" is "a profiler session is active" (``profile_trace`` here, or
``jax.profiler.start_trace``); without one the annotation is a no-op in the
runtime and the ring is all that is kept.

This module does not import JAX: ``isolation/guard.py`` is imported before
``import jax`` on purpose (``apply_hbm_cap``) and in processes that never
touch it.  The annotation is taken only once ``jax`` is in ``sys.modules``.

Where a phase has a counter, the span is how the counter is fed::

    with span("kubeshare.engine.admit") as s:
        self._admit()
    self.host_seconds["admit"] += s.seconds

**Every span, its attributes, and who reads each.**  A reader is a per-layer
metric of the benchmark (``chipbench/layer_metrics``: M), a counter of the
metrics plane (``collect_metrics``: C) or the slow-dispatch WARNING line
(``ServingEngine._report_slow_dispatch``: W).  A span or an attribute that
none of them reads is not kept (PR 38 took out ``kubeshare.engine.step``
and its ``i``, ``reach`` on the launch, ``pod`` on the client's span).

=============================  ============================================
``kubeshare.engine.admit``     M ``engine.schedule_ms_per_dispatch.*``; C
                               ``host_seconds{admit}``.  ``queued`` (the
                               queue's depth on entry), ``admitted``,
                               ``matched_rows`` (prefix rows matched by this
                               call): the ``admit`` key of the benchmark's
                               ``program_stages`` line, beside that metric
``kubeshare.engine.consume``   C ``host_seconds{consume}``
``kubeshare.engine.fetch``     M ``engine.fetch_ms_per_dispatch.*``
``kubeshare.engine.tune``      M ``engine.schedule_ms_*``; C ``{tune}``
``kubeshare.engine.plan``      M ``engine.schedule_ms_*``; C ``{plan}``
``kubeshare.engine.dispatch``  C ``host_seconds{dispatch}``
``kubeshare.engine.marshal``   M ``engine.marshal_ms_per_dispatch.*``
``kubeshare.engine.launch``    M: every ``*_per_dispatch`` metric divides by
                               their count.  ``kind`` (M: a ``copy`` or
                               ``upload`` is no dispatch; the module a launch
                               ran; W), ``lanes`` and ``chunk`` (W), ``rows``
                               and ``attend`` (M
                               ``step.attend_kernel_hbm_roofline.*``: the
                               rows the kernels had to read, where the lanes
                               ran one), ``program`` (M ``step.stage_ms.*``
                               and the rest of ``_stages.py``: the table its
                               operations are booked by; W), ``experts`` (a
                               routed engine's alone: ``kernel`` / ``loop``,
                               what computes the expert layers' tiles; W),
                               ``weight_passes`` and, set once the call
                               has returned, ``host_args`` / ``host_bytes``
                               (C ``weight_passes{kind}``, ``host_args``,
                               ``host_arg_bytes``: the passes over the
                               layers the program made, and the host
                               arrays its call carried to the device — ONE
                               packed buffer a planned launch)
``kubeshare.engine.device_wait``  M ``_stages.py`` (a launch's device time
                               ends with it); W
``kubeshare.engine.routing``   M ``moe.*``, ``step.*routed*``,
                               ``step.experts_hbm_roofline.backlog``: ``rows``
                               ``live`` ``passes`` ``held`` ``zero``
                               ``absent`` ``touched`` ``tiles`` ``tile_rows``
``kubeshare.engine.diffusion`` M ``diffusion.*``, ``step.diffusion_*``:
                               ``lanes`` ``passes`` ``commit_passes`` ``rows``
                               ``masked_rows`` ``committed`` ``blocks_done``
                               ``kv_rows`` ``chunk`` ``touched``
``kubeshare.guard.acquire``    M ``guard.broker_wait_ms`` (``pod``,
                               ``broker``); C ``guard_wait_seconds``; W
                               (``broker``)
``kubeshare.guard.gated``      M ``dispatch.gated_idle_ms.*``,
                               ``guard.cotenant_wall_over_device`` (``pod``)
``kubeshare.client.acquire``   W (``round_trips``: how often tokend said
                               WAIT before the grant)
=============================  ============================================
"""

from __future__ import annotations

import collections
import contextlib
import glob
import json
import os
import sys
import threading
import time
from typing import Any, Deque, Dict, Iterator, List, Optional, Tuple

__all__ = ["STAGES_FILE", "profile_trace", "span", "spans"]

STAGES_FILE = "kubeshare_stages.json"

# (name, start, end, thread name, attrs): start and end on time.monotonic()
_Record = Tuple[str, float, float, str, Dict[str, Any]]

_RING_SIZE = 4096  # about 400 dispatches of the serving engine
_ring: Deque[_Record] = collections.deque(maxlen=_RING_SIZE)
_ring_lock = threading.Lock()
_annotation = None  # jax.profiler.TraceAnnotation, once jax is imported


def _annotation_class():
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        # a half-imported jax has no profiler yet: ask again next time
        _annotation = getattr(getattr(jax, "profiler", None),
                              "TraceAnnotation", None)
    return _annotation


class span:
    """A named interval.  A context manager; one that has to start in one
    function and end in another is entered and exited by hand.  ``start``,
    ``end`` and ``seconds`` read after the exit; ``set`` adds attributes
    that are known only inside the block."""

    __slots__ = ("name", "attrs", "start", "end", "_trace")

    def __init__(self, name: str, **attrs: Any) -> None:
        self.name = name
        self.attrs = attrs
        self.start = self.end = 0.0
        self._trace = None

    def __enter__(self) -> "span":
        annotation = _annotation_class()
        if annotation is not None:
            self._trace = annotation(self.name, **self.attrs)
            self._trace.__enter__()
        self.start = time.monotonic()
        return self

    def set(self, **attrs: Any) -> None:
        self.attrs.update(attrs)
        if self._trace is not None:
            self._trace.set_metadata(**attrs)

    def __exit__(self, *exc: Any) -> None:
        self.end = time.monotonic()
        if self._trace is not None:
            self._trace.__exit__(*exc)
            self._trace = None
        record = (self.name, self.start, self.end,
                  threading.current_thread().name, self.attrs)
        with _ring_lock:
            _ring.append(record)

    @property
    def seconds(self) -> float:
        return self.end - self.start


def spans(since: Optional[float] = None,
          name: Optional[str] = None) -> List[_Record]:
    """The ring's finished spans, oldest first: those that started at or
    after ``since`` (a ``time.monotonic()`` instant) and are called
    ``name``."""
    with _ring_lock:
        records = list(_ring)
    return [r for r in records
            if (since is None or r[1] >= since)
            and (name is None or r[0] == name)]


@contextlib.contextmanager
def profile_trace(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a profiler trace into ``log_dir`` (no-op when None): the
    device's operations and every ``span`` of this process, on one clock.
    On leaving, ``kubeshare_stages.json`` is written beside the
    ``.xplane.pb``: for every step program a ``kubeshare.engine.launch`` of
    the session named (of those still in the ring: its last 400 dispatches
    or so), the table {instruction name: stage} by which the file's ``XLA
    Ops`` events are booked to stages (``serving/stages.py``)."""
    if not log_dir:
        yield
        return
    import jax

    started = time.monotonic()
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
    _write_stage_tables(log_dir, started)


def _write_stage_tables(log_dir: str, since: float) -> None:
    stages = sys.modules.get("kubeshare_tpu.serving.stages")
    if stages is None:
        return  # this process warmed no step program
    launched = {r[4].get("program")
                for r in spans(since=since, name="kubeshare.engine.launch")}
    tables = {name: table for name in sorted(launched - {None})
              if (table := stages.stage_table(name)) is not None}
    if not tables:
        return
    traces = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    where = os.path.dirname(traces[-1]) if traces else log_dir
    with open(os.path.join(where, STAGES_FILE), "w") as f:
        json.dump({"programs": tables}, f)
