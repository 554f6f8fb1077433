"""Rows a held expert got in one expert-layer pass of one layer: held assignments
over passes x layers x experts held, from the ``kubeshare.engine.routing``
spans of the traced tail.  How far each expert's load is from the deployment's
(32 ranks would send 32 times as many)."""

LAYER = "step programs"
UNIT = "rows"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._routing import held_rows_per_expert

    return held_rows_per_expert(run)
