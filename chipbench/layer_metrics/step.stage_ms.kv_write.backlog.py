"""Device milliseconds a planned launch spends in the pool write (scope
``kv_write``: the rows' scatter, and the loop of row updates the compiler
expands it into): the ``XLA Ops`` events of the traced tail booked by the
program's own table of stages (``serving/stages.py``) (backlog cells)."""

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._stages import stage_ms

    return stage_ms(run, "kv_write")
