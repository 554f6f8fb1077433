"""The retention mechanism's least bytes (states read, unfolded rows, folds:
``kubeshare.engine.retention`` spans x the configuration's counts) over all
the same dispatches must read (those, and the weights once a pass of a
span): how much of a step IS the mechanism."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._retention import state_bytes_share

    return state_bytes_share(run)
