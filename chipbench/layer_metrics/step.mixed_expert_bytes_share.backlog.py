"""The touched experts' part of a mixed dispatch's least bytes: ``touched x
expert_bytes`` over what ``step.mixed_routed_hbm_roofline.backlog`` divides by
(the weights outside the experts once a decode step, the live rows, the touched
experts).  Says whether the expert matrices are most of what a step must move."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._tiles import mixed_expert_bytes_share

    return mixed_expert_bytes_share(run)
