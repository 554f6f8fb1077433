"""Device-busy milliseconds per mixed dispatch (rate cells)."""

LAYER = "step programs"
UNIT = "ms"
MOVES = "ttft_tail_ms"


def read(run):
    from chipbench.layer_metrics._readers import mixed_device_ms

    return mixed_device_ms(run)
