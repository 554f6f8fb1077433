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
"""

from __future__ import annotations

import collections
import contextlib
import sys
import threading
import time
from typing import Any, Deque, Dict, Iterator, List, Optional, Tuple

__all__ = ["profile_trace", "span", "spans"]

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
    device's operations and every ``span`` of this process, on one clock."""
    if not log_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
