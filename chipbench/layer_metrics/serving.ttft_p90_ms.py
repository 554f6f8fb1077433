"""90th percentile of time to first token from the due instant, over the
requests whose first token came inside the window (the profiler's stop falls
into the drain of a traced run).  Spreads by 7% over undisturbed runs of one
schedule at 64 requests, three times what the slowest tenth's mean does."""

LAYER = "serving scheduler"
UNIT = "ms"
MOVES = "ttft_tail_ms"


def read(run):
    return run["notes"].get("ttft_p90_in_window_ms")
