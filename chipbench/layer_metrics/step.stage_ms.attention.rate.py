"""Device milliseconds a planned launch spends in attention — the projections,
the scores and the paged kernels (scopes ``attention``, ``kv_view``,
``qk_norm``, ``mla``): the ``XLA Ops`` events of the traced tail booked by
the program's own table of stages (``serving/stages.py``) (rate cells)."""

LAYER = "step programs"
UNIT = "ms"
MOVES = "token_gap_mean_ms"


def read(run):
    from chipbench.layer_metrics._stages import stage_ms

    return stage_ms(run, "attention")
