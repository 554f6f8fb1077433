"""Engine host milliseconds per dispatch (backlog cells)."""

LAYER = "serving scheduler"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._readers import host_ms_per_dispatch

    return host_ms_per_dispatch(run)
