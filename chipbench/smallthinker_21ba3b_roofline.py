"""The count of bytes of ``configs/smallthinker-21ba3b-instruct.json``: what
one token caches, by layer kind, and the least a decode step must read.

The cache row is a K and a V of ``head_width`` values a KV head a layer
(2 x 4 x 128 values = 2,048 B a layer in bf16), the same in both kinds of
layer; what differs is how long a row is HELD and how many rows a step
READS.  A full layer ("global": every earlier row, nothing rotated) holds
and reads every row of a lane's context; a window layer holds and reads the
``attention_window`` rows behind the query at most.  So the pool's size is
counted over all the layers (``kv_bytes_per_row``: 16,384 B, what
``system.build_engine`` sizes the pool by and holds it to), and what a step
reads a kind at a time (``kv_read_bytes_by_kind``: 4,096 B a row the 2 full
layers, 12,288 B a row the 6 window layers), from the two row counts the
program's launch span gives: ``rows`` (the lanes' contexts) and
``window_rows`` (each lane's ``min(rows held, window)``).

A decode step must read everything outside the routed experts — every
layer's attention, its two norms and its router; the final norm and the head
— the rows its lanes' layers see, **and no routed expert**: which of them a
step reads is its routing's, not the shapes'.  ``decode_step_min_bytes``
counts the FULL kind's rows alone for the cached part (it is handed one row
count, the contexts'; a window layer reads no more than that and often far
less), so a share of a roofline worked out from it is a floor that cannot
overshoot; ``step_bytes_by_kind`` is the count for a reader that knows both
row counts and the experts touched
(``step.mixed_kinds_routed_hbm_roofline.backlog``).
"""

from __future__ import annotations

from typing import Dict

from chipbench.roofline import _itemsize


def operators(tc: Dict, name: str) -> int:
    return sum(1 for operator in tc["layer_operators"] if operator == name)


def kv_heads(tc: Dict) -> int:
    return tc.get("n_kv_heads") or tc["n_heads"]


def attention_weight_count(tc: Dict) -> int:
    """One attention: q and the output projection, k and v; no norm, no
    bias."""
    d, hd = tc["d_model"], tc["head_width"]
    return 2 * d * tc["n_heads"] * hd + 2 * d * kv_heads(tc) * hd


def outside_experts_count(tc: Dict) -> int:
    """Every layer without its routed experts."""
    d = tc["d_model"]
    return tc["n_layers"] * (attention_weight_count(tc) + 2 * d
                             + d * tc["n_routed_experts"])


def parameter_count(tc: Dict) -> int:
    """Everything the stage holds."""
    d = tc["d_model"]
    return (outside_experts_count(tc)
            + tc["n_layers"] * tc["n_routed_experts"]
            * 3 * d * tc["expert_d_ff"]
            + d + 2 * d * tc["vocab_size"])


def expert_bytes(tc: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * tc["d_model"] * tc["expert_d_ff"] * _itemsize(tc)


def decode_step_weight_bytes(tc: Dict) -> int:
    count = outside_experts_count(tc)
    count += tc["d_model"] + tc["d_model"] * tc["vocab_size"]
    return count * _itemsize(tc)


def kv_row_bytes_a_layer(tc: Dict) -> int:
    return 2 * kv_heads(tc) * tc["head_width"] * _itemsize(tc)


def kv_bytes_per_row(tc: Dict) -> int:
    """What the pool is sized by: a row of EVERY layer, both kinds."""
    return tc["n_layers"] * kv_row_bytes_a_layer(tc)


def kv_read_bytes_by_kind(tc: Dict) -> Dict[str, int]:
    """What one cached row costs a step to read, in the layers of each
    kind: the full kind's a row of the lane's context, the window kind's a
    row of its window."""
    row = kv_row_bytes_a_layer(tc)
    window = operators(tc, "window")
    return {"full": (tc["n_layers"] - window) * row, "window": window * row}


def decode_step_min_bytes(tc: Dict, live_rows: float) -> float:
    """The least a decode step must read, from ONE row count (the lanes'
    contexts): the weights outside the routed experts once and those rows in
    the full layers; what the window layers read of them is left out (it is
    at most as many), so this cannot overshoot."""
    return (decode_step_weight_bytes(tc)
            + kv_read_bytes_by_kind(tc)["full"] * live_rows)


def step_bytes_by_kind(tc: Dict, rows: float, window_rows: float) -> float:
    """One decode step's bytes outside the routed experts, from both row
    counts of a launch span: the weights once, ``rows`` in the full layers,
    ``window_rows`` in the window layers."""
    read = kv_read_bytes_by_kind(tc)
    return (decode_step_weight_bytes(tc) + read["full"] * rows
            + read["window"] * window_rows)
