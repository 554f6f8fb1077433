"""The short convolutions' share of their roofline: the least time the
``kubeshare.engine.conv`` spans imply (every pass the larger of the six
operators' weights once and its lanes' states read and written at the chip's
HBM rate, and its rows' multiply-adds at its bf16 peak: the configuration's
``conv_min_seconds``) over the device seconds the program's table books to
stage ``conv``."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._conv import conv_roofline

    return conv_roofline(run)
