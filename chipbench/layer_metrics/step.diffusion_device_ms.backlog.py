"""Device-busy milliseconds of one dispatch that carries diffusion lanes and
no chunk (the harness's kind ``decode``): one pass over every lane's block."""

LAYER = "step programs"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._diffusion import diffusion_device_ms

    return diffusion_device_ms(run)
