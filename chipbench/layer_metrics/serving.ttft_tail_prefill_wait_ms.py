"""Of the slowest tenth by time to first token, among the requests whose first
token came inside the window: the mean milliseconds from admitted to the launch
of the first dispatch that carried a chunk of the prompt (``first_dispatch_at -
admitted_at``): waiting for a prefill turn among the filling slots."""

LAYER = "serving scheduler"
UNIT = "ms"
MOVES = "ttft_tail_ms"


def read(run):
    from chipbench.layer_metrics._spans import ttft_tail_parts

    parts = ttft_tail_parts(run)
    return None if parts is None else parts["prefill_wait"]
