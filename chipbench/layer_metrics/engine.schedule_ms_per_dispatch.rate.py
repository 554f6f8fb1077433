"""Engine milliseconds of scheduling per dispatch: the program's own
``kubeshare.engine.admit``, ``.tune`` and ``.plan`` spans over its launches, in
the traced tail of the window (rate cells)."""

LAYER = "serving scheduler"
UNIT = "ms"
MOVES = "token_gap_mean_ms"


def read(run):
    from chipbench.layer_metrics._spans import SCHEDULE, ms_per_dispatch

    return ms_per_dispatch(run, SCHEDULE)
