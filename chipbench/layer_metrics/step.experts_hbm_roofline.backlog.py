"""The expert stage's share of its HBM roofline: the experts the routing
touched (``kubeshare.engine.routing`` spans) x the configuration's bytes an
expert, at the chip's HBM rate, over the device seconds the program's table
books to stage ``experts``."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._stages import experts_hbm_roofline

    return experts_hbm_roofline(run)
