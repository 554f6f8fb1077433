"""90th percentile, over the requests that finished inside the window, of the
mean gap between their tokens."""

LAYER = "serving scheduler"
UNIT = "ms"
MOVES = "token_gap_mean_ms"


def read(run):
    return run["notes"].get("token_gap_p90_in_window_ms")
