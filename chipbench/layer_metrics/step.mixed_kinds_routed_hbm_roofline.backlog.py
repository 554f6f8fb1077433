"""A mixed dispatch's share of its HBM roofline where the model caches by
layer kind: ``decode_span`` x (the weights outside the routed experts + the
decode lanes' rows in the full layers + their ``window_rows`` in the window
layers) + the touched experts' matrices, at the chip's HBM rate, over the
device time of the mixed dispatches of the traced tail."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._kinds import mixed_kinds_routed_hbm_roofline

    return mixed_kinds_routed_hbm_roofline(run)
