"""Milliseconds of each gated interval of pod A (``kubeshare.guard.gated``: what
tokend charges) in which the device ran nothing: launch and completion latency
(rate cell, pod A alone: beside pod B its operations fill the gap)."""

LAYER = "token runtime"
UNIT = "ms"
MOVES = "token_gap_mean_ms"


def read(run):
    from chipbench.layer_metrics._spans import gated_idle_ms

    return gated_idle_ms(run)
