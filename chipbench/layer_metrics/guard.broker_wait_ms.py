"""Mean milliseconds of pod A's ``kubeshare.guard.acquire`` spans that went to the
token client (``broker=1``): the round trip to tokend through pmgr."""

LAYER = "token runtime"
UNIT = "ms"
MOVES = "token_gap_mean_ms"


def read(run):
    from chipbench.layer_metrics._spans import broker_wait_ms

    return broker_wait_ms(run)
