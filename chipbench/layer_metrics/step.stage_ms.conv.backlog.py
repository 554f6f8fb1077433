"""Device milliseconds a planned launch spends in the gated short
convolutions (scopes ``short_conv``: the projections, the gates and the
filter; ``conv_state``: the state's read, shift and write): the ``XLA Ops``
events of the traced tail booked by the program's own table of stages
(``serving/stages.py``) (backlog cells)."""

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._stages import stage_ms

    return stage_ms(run, "conv")
