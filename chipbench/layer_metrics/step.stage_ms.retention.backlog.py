"""Device milliseconds a planned launch spends in the retention mechanism
(scopes ``retention_state``, ``retention_tail``, ``retention_fold``, ``gate``,
``phi``): the ``XLA Ops`` events of the traced tail booked by the program's
own table of stages (``serving/stages.py``) (backlog cells)."""

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._stages import stage_ms

    return stage_ms(run, "retention")
