"""Engine host milliseconds per dispatch (rate cells)."""

LAYER = "serving scheduler"
UNIT = "ms"
MOVES = "token_gap_mean_ms"


def read(run):
    from chipbench.layer_metrics._readers import host_ms_per_dispatch

    return host_ms_per_dispatch(run)
