"""A decode step's share of its HBM roofline (rate cells)."""

LAYER = "step programs"
UNIT = "%"
MOVES = "token_gap_mean_ms"


def read(run):
    from chipbench.layer_metrics._readers import decode_hbm_roofline

    return decode_hbm_roofline(run)
