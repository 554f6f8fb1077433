"""Shared PromQL-style readers over the serving metrics plane.

Every consumer that reasons about the engine's Prometheus-shaped
families — the fleet autoscaler diffing TTFT histogram intervals, the
online autotuner diffing dispatch counters between ticks, a scraper
computing quantiles from a snapshot — needs the same three
primitives:

- **interval diffing**: counters and histogram bucket counts are
  cumulative; a policy wants the delta over its own observation window
  (PromQL's ``increase()``), tracked per consumer so two readers never
  clobber each other's baselines;
- **quantile estimation**: histogram bucket counts → an upper-bound (or
  interpolated) quantile, the ``histogram_quantile()`` analogue;
- **snapshot flattening**: a list of metric families → a flat
  ``{(name, labels): value}`` dict that label-subset sums and histogram
  merges read from.

This module owns those primitives. It deliberately imports nothing from
:mod:`engine` (or anywhere else in the serving package): bucket bounds
are always explicit parameters, and the windows operate on plain lists
and dicts, so the tuner, the autoscaler and a scraper can all depend on it
without import cycles.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "CounterWindow",
    "HistogramWindow",
    "flatten_metrics",
    "hist_quantile",
    "interval_quantile",
    "metric_histogram",
    "metric_value",
]


def interval_quantile(counts: Sequence[float], q: float,
                      bounds: Sequence[float]) -> float:
    """Upper-bound quantile estimate from histogram bucket counts.

    ``counts`` is one count per bucket of ``bounds`` plus a final
    overflow bucket (the ``+Inf`` tail); the estimate is the upper bound
    of the bucket the rank falls in, matching Prometheus's
    ``histogram_quantile`` convention of charging an observation to its
    bucket ceiling.  Returns ``inf`` when the rank lands in the overflow
    bucket and ``0.0`` on an empty interval.
    """
    total = sum(counts)
    if total <= 0:
        return 0.0
    rank = q * total
    cum = 0.0
    for i, c in enumerate(counts):
        cum += c
        if c and cum >= rank:
            return float(bounds[i]) if i < len(bounds) else float("inf")
    return float("inf")


class HistogramWindow:
    """Interval view over a cumulative histogram's bucket counts.

    Each consumer holds its OWN window; :meth:`update` takes the latest
    cumulative per-bucket counts and returns the increase since this
    window's previous update.  The first call diffs against zero — a
    counter appearing IS an increase from zero, the PromQL
    ``increase()`` convention (and the fleet autoscaler's original
    inline behavior, preserved exactly).
    """

    def __init__(self) -> None:
        self._prev: Optional[List[float]] = None

    def update(self, cumulative: Sequence[float]) -> List[float]:
        snap = list(cumulative)
        prev = self._prev if self._prev is not None else [0] * len(snap)
        self._prev = snap
        return [a - b for a, b in zip(snap, prev)]

    def quantile(self, cumulative: Sequence[float], q: float,
                 bounds: Sequence[float]) -> Tuple[float, float]:
        """Advance the window and return ``(interval_count, quantile)``."""
        interval = self.update(cumulative)
        return sum(interval), interval_quantile(interval, q, bounds)


class CounterWindow:
    """Interval view over a dict of cumulative scalar counters.

    :meth:`update` takes the latest cumulative values and returns the
    per-key increase since the previous update; keys appearing for the
    first time (the very first call included) diff against zero, like
    :class:`HistogramWindow`.
    """

    def __init__(self) -> None:
        self._prev: Dict[str, float] = {}

    def update(self, cumulative: Dict[str, float]) -> Dict[str, float]:
        snap = dict(cumulative)
        out = {k: v - self._prev.get(k, 0.0) for k, v in snap.items()}
        self._prev = snap
        return out


def flatten_metrics(families) -> dict:
    """Flatten metric families into ``{(name, sorted_labels): value}``.

    ``families`` is the list returned by an engine/router/fleet
    ``collect_metrics()``; the result is the flat dict
    :func:`metric_value` and :func:`metric_histogram` read from.
    """
    return {(sm.name, tuple(sorted(sm.labels.items()))): sm.value
            for f in families for sm in f.samples}


def metric_value(metric: dict, name: str, **want):
    """Sum of samples named ``name`` whose labels match every ``want``."""
    return sum(v for (n, labels), v in metric.items()
               if n == name
               and all(dict(labels).get(k) == w for k, w in want.items()))


def metric_histogram(metric: dict, name: str):
    """Merge ``name + "_bucket"`` series into sorted ``[(le, cum)]``."""
    buckets = {}
    for (n, labels), v in metric.items():
        if n != name + "_bucket":
            continue
        le = dict(labels)["le"]
        le = float("inf") if le == "+Inf" else float(le)
        buckets[le] = buckets.get(le, 0) + v
    return sorted(buckets.items())


def hist_quantile(buckets, q: float):
    """Interpolated quantile from :func:`metric_histogram` buckets.

    Linear interpolation inside the bucket the rank falls in (the
    smoother convention); an observation in the ``+Inf`` tail
    reports the highest finite bound.  Returns ``None`` on an empty
    histogram.
    """
    if not buckets or buckets[-1][1] <= 0:
        return None
    target = q * buckets[-1][1]
    prev_le, prev_cum = 0.0, 0.0
    for le, cum in buckets:
        if cum >= target:
            if le == float("inf"):
                return prev_le
            return prev_le + (le - prev_le) * (target - prev_cum) \
                / max(1e-12, cum - prev_cum)
        prev_le, prev_cum = le, cum
    return prev_le
