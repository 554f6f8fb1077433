"""Held experts that got at least one row, of passes x layers x experts held
(``kubeshare.engine.routing`` spans of the traced tail): the share of the
rank's expert weights a pass had to read."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._routing import held_touched_share

    return held_touched_share(run)
