"""Engine milliseconds per dispatch reading the previous dispatch's device
results back (``kubeshare.engine.fetch``, inside the consume phase, apart from
its bookkeeping) (backlog cell)."""

LAYER = "serving scheduler"
UNIT = "ms"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.layer_metrics._spans import ms_per_dispatch

    return ms_per_dispatch(run, ("engine.fetch",))
