"""Of the device-busy seconds inside the engine's programs, the part the
program's table of stages (``serving/stages.py``) books to no stage: how far
``step.stage_ms.*`` can be trusted (backlog cells)."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._stages import unscoped_share

    return unscoped_share(run)
