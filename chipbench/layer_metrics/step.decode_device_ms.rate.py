"""Device-busy milliseconds per decode step (rate cells)."""

LAYER = "step programs"
UNIT = "ms"
MOVES = "token_gap_mean_ms"


def read(run):
    from chipbench.layer_metrics._readers import decode_device_ms

    return decode_device_ms(run)
