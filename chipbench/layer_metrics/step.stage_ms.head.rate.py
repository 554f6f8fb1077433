"""Device milliseconds a planned launch spends in the head (scopes ``lm_head``,
``sample``, ``denoise_pick``): the ``XLA Ops`` events of the traced tail
booked by the program's own table of stages (``serving/stages.py``) (rate
cells)."""

LAYER = "step programs"
UNIT = "ms"
MOVES = "token_gap_mean_ms"


def read(run):
    from chipbench.layer_metrics._stages import stage_ms

    return stage_ms(run, "head")
