"""Engine milliseconds of argument marshalling per dispatch: the program's
``kubeshare.engine.marshal`` span (from the plan to the guard: the lanes'
tables and the ``jnp.asarray`` of each argument) over its launches (rate cells)."""

LAYER = "serving scheduler"
UNIT = "ms"
MOVES = "token_gap_mean_ms"


def read(run):
    from chipbench.layer_metrics._spans import ms_per_dispatch

    return ms_per_dispatch(run, ("engine.marshal",))
