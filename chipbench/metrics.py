"""End-to-end arithmetic: percentiles and open-loop times."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) with linear interpolation between
    closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of nothing")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def slowest_tenth_mean(values: Sequence[float]) -> float:
    """The mean of the slowest tenth (rounded up to a whole request): a tail
    over all requests that moves by a share of one request's time when one
    request moves, where a percentile jumps by all of it."""
    if not values:
        raise ValueError("tail of nothing")
    k = max(1, math.ceil(len(values) / 10))
    return sum(sorted(values)[-k:]) / k


def ttft_seconds(opened_at: float, due: float, first_token_at: Optional[float],
                 window: float) -> float:
    """Time to first token from the instant the request was DUE (open
    loop); a request with no first token counts as the window's length."""
    if first_token_at is None:
        return window
    return first_token_at - (opened_at + due)


def token_gap_seconds(first_token_at: Optional[float],
                      finished_at: Optional[float], tokens: int
                      ) -> Optional[float]:
    """Mean gap between a request's output tokens; None where it has no
    gap (one token, or not finished)."""
    if first_token_at is None or finished_at is None or tokens < 2:
        return None
    return (finished_at - first_token_at) / (tokens - 1)
