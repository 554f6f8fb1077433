"""How full the expert loop's tiles ran: held assignments over the rows of the
tiles the loop ran, padding included (``held / tile_rows`` of the
``kubeshare.engine.routing`` spans of the traced tail).  The rest is rows of
zeros the MXU multiplies beside the real ones."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._tiles import tile_fill_share

    return tile_fill_share(run)
