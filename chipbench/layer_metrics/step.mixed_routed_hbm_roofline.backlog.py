"""A mixed dispatch's share of its HBM roofline with the experts its routing
touched added to the least bytes (``step.mixed_hbm_roofline.backlog`` counts
no expert): device trace x the ``kubeshare.engine.routing`` spans x the
configuration's ``expert_bytes``."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._routing import mixed_routed_hbm_roofline

    return mixed_routed_hbm_roofline(run)
