"""Of the slowest tenth by time to first token, among the requests whose first
token came inside the window: the mean milliseconds from the launch of the first
dispatch that carried a chunk of the prompt to the first token (``first_token_at
- first_dispatch_at``): its own chunks, one a dispatch, and the turns between."""

LAYER = "serving scheduler"
UNIT = "ms"
MOVES = "ttft_tail_ms"


def read(run):
    from chipbench.layer_metrics._spans import ttft_tail_parts

    parts = ttft_tail_parts(run)
    return None if parts is None else parts["prefill"]
