"""Rows a touched expert got: held assignments over the held experts that got
at least one row, summed over passes and layers, from the
``kubeshare.engine.routing`` spans of the traced tail.  Needs no layer count.
A touched expert's three matrices are read for that many rows: 1 is a decode
pass in which no two lanes agree, a chunk's rows over the experts it reaches
is the most a cell can give."""

LAYER = "step programs"
UNIT = "rows"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._tiles import rows_per_touched_expert

    return rows_per_touched_expert(run)
