"""Device-busy milliseconds per mixed dispatch (backlog cells)."""

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._readers import mixed_device_ms

    return mixed_device_ms(run)
