"""A mixed dispatch's share of its HBM roofline (rate cells)."""

LAYER = "step programs"
UNIT = "%"
MOVES = "ttft_tail_ms"


def read(run):
    from chipbench.layer_metrics._readers import mixed_hbm_roofline

    return mixed_hbm_roofline(run)
