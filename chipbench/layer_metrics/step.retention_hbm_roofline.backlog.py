"""The retention stage's share of its HBM roofline: the least bytes the
``kubeshare.engine.retention`` spans imply (a state a lane-pass that read
one, the unfolded rows, two states and a key block of rows a fold: the
configuration's ``retention_min_bytes``) at the chip's HBM rate, over the
device seconds the program's table books to stage ``retention``."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._retention import retention_hbm_roofline

    return retention_hbm_roofline(run)
