"""The paged attention kernels' share of their HBM roofline: the cached
rows the lanes held at launch x the configuration's bytes a row x the
dispatch's kernel passes, at the chip's HBM rate, over the seconds of the
``attention`` operations named ``paged_*`` (rate cells)."""

LAYER = "step programs"
UNIT = "%"
MOVES = "token_gap_mean_ms"


def read(run):
    from chipbench.layer_metrics._stages import attend_kernel_hbm_roofline

    return attend_kernel_hbm_roofline(run)
