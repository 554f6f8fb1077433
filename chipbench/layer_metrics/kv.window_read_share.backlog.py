"""What a window layer reads of what a full layer reads: the launch spans'
``window_rows`` over their ``rows``, over the decode lanes of the traced
tail, in percent.  100 = the window never binds."""

LAYER = "serving scheduler"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._kinds import window_read_share

    return window_read_share(run)
