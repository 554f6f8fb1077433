"""The paged attention kernel's share of its HBM roofline under a window:
the decode lanes' rows x the full layers' bytes a row + their ``window_rows``
x the window layers', once a step of the span, at the chip's HBM rate, over
the seconds of the ``attention`` operations named ``paged_*``."""

LAYER = "step programs"
UNIT = "%"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._kinds import attend_kinds_kernel_hbm_roofline

    return attend_kinds_kernel_hbm_roofline(run)
