"""Mean wait in guard.acquire, timed by the proxy the benchmark wraps around
the guard it hands the engine."""

LAYER = "token runtime"
UNIT = "ms"
MOVES = "token_gap_mean_ms"


def read(run):
    c = run["record"]["counters"]
    if c["acquire_calls"] <= 0:
        return None
    return c["acquire_wait_s"] / c["acquire_calls"] * 1e3
