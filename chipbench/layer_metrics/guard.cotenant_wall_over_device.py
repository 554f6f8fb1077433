"""Pod B's charged wall time over its device time: the seconds of its
``kubeshare.guard.gated`` spans over the device time of the programs that are
not the engine's (``XLA Modules`` not named ``jit_kubeshare_*``) inside them.
1 would be a co-tenant charged what the chip gave it."""

LAYER = "token runtime"
UNIT = "x"
MOVES = "cotenant_tflops"


def read(run):
    from chipbench.layer_metrics._spans import cotenant_wall_over_device

    return cotenant_wall_over_device(run)
