"""Of the slowest tenth by time to first token, among the requests whose first
token came inside the window: the mean milliseconds from submitted to admitted
(``admitted_at - submitted_at``): waiting for a slot."""

LAYER = "serving scheduler"
UNIT = "ms"
MOVES = "ttft_tail_ms"


def read(run):
    from chipbench.layer_metrics._spans import ttft_tail_parts

    parts = ttft_tail_parts(run)
    return None if parts is None else parts["queue"]
