"""Lane-passes a finished diffusion block took (``(passes + commit_passes) /
blocks_done`` of the ``kubeshare.engine.diffusion`` spans of the traced
tail): 5 by the published loop at 4 steps, a commit pass among them; the
passes over a request's last block, which is never committed, count above
the line only."""

LAYER = "serving scheduler"
UNIT = "passes"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._diffusion import passes_per_block

    return passes_per_block(run)
