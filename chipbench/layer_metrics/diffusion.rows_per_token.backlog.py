"""Query rows the diffusion lanes computed for each token they served
(``rows / committed`` of the ``kubeshare.engine.diffusion`` spans of the
traced tail): 5.0 by the published loop at 4 steps a block of 4 — four
denoising passes and the commit pass of 4 rows each for 4 tokens — less
where a block needs no commit pass (a request's last), more where a block's
rows are already known (a prompt's tail) or never served (past the budget)."""

LAYER = "serving scheduler"
UNIT = "rows"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._diffusion import rows_per_token

    return rows_per_token(run)
