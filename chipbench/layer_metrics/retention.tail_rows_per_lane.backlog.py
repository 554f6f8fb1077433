"""Unfolded rows a lane read as keys and values a pass (``tail_rows`` of the
``kubeshare.engine.retention`` spans over their lane-passes): what the paged
tails hold, whatever the requests' lengths, when lanes fold as planned."""

LAYER = "serving scheduler"
UNIT = "rows"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._retention import tail_rows_per_lane

    return tail_rows_per_lane(run)
