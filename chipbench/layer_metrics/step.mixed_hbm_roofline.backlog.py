"""A mixed dispatch's share of its HBM roofline (backlog cells)."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._readers import mixed_hbm_roofline

    return mixed_hbm_roofline(run)
