"""The count of bytes and operations of ``configs/lfm2-24b-a2b.json``: what
one token caches, the least a decode step must read, and the least time the
short convolutions' stage can take.

The cache row is a K and a V a KV head of the ATTENTION layers alone (2 of
the stage's 8: 2 x 2 x 8 x 64 values = 4,096 B a token in bf16); a
convolution layer caches nothing a token: its state is ``conv_taps - 1`` rows
of ``d_model`` values a LANE, by slot beside the pool (6 x 2 x 2048 x 2 B =
49,152 B a lane).  A decode step must read everything outside the routed
experts — every operator, the norms, the leading layers' dense FFNs, every
expert layer's router with its bias, the final norm and the head — the live
lanes' cached rows and (where the caller says how many lanes) their states
read and written, **and no routed expert**: which of them a step reads is its
routing's, not the shapes'.  So a share of a roofline worked out from
``decode_step_min_bytes`` reads low and never over 100%; ``expert_bytes`` is
what each touched expert adds, for a reader that knows how many were
(``step.mixed_routed_hbm_roofline.backlog``,
``step.mixed_expert_bytes_share.backlog``).

``conv_min_seconds`` is the mechanism's own roofline, from what the program's
``kubeshare.engine.conv`` span says a dispatch carried: every pass over the
six operators (a decode step of the span; the chunk) must read their weights
once and read and write its lanes' states, and multiply its rows through
them; a pass takes the larger of the two, at the chip's peaks.  A 512-row
chunk is compute-bound (103 GFLOP against 201 MB), a pass of 32 rows is not.
"""

from __future__ import annotations

from typing import Dict

from chipbench.roofline import _itemsize


def operators(tc: Dict, name: str) -> int:
    return sum(1 for operator in tc["layer_operators"] if operator == name)


def kv_heads(tc: Dict) -> int:
    return tc.get("n_kv_heads") or tc["n_heads"]


def conv_weight_count(tc: Dict) -> int:
    """One short convolution: ``w_in``, ``w_out`` and the filter."""
    d = tc["d_model"]
    return d * 3 * d + d * d + d * tc["conv_taps"]


def attention_weight_count(tc: Dict) -> int:
    d, hd = tc["d_model"], tc["head_width"]
    return (2 * d * tc["n_heads"] * hd + 2 * d * kv_heads(tc) * hd + 2 * hd)


def expert_layers(tc: Dict) -> int:
    return tc["n_layers"] - tc.get("first_dense_layers", 0)


def router_count(tc: Dict) -> int:
    outputs = tc["n_routed_experts"]
    return tc["d_model"] * outputs \
        + (outputs if tc.get("router_choice_bias") else 0)


def outside_experts_count(tc: Dict) -> int:
    """Every layer without its routed experts."""
    d = tc["d_model"]
    return (operators(tc, "conv") * conv_weight_count(tc)
            + operators(tc, "attention") * attention_weight_count(tc)
            + tc["n_layers"] * 2 * d
            + tc.get("first_dense_layers", 0) * 3 * d * tc["d_ff"]
            + expert_layers(tc) * router_count(tc))


def parameter_count(tc: Dict) -> int:
    """Everything the stage holds: the layers, every routed expert, the final
    norm, the embedding and the head."""
    d = tc["d_model"]
    return (outside_experts_count(tc)
            + expert_layers(tc) * tc["n_routed_experts"]
            * 3 * d * tc["expert_d_ff"]
            + d + 2 * d * tc["vocab_size"])


def expert_bytes(tc: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * tc["d_model"] * tc["expert_d_ff"] * _itemsize(tc)


def decode_step_weight_bytes(tc: Dict) -> int:
    count = outside_experts_count(tc)
    count += tc["d_model"] + tc["d_model"] * tc["vocab_size"]
    return count * _itemsize(tc)


def kv_bytes_per_row(tc: Dict) -> int:
    """What the pool holds a token: a K and a V a KV head of the layers
    whose operator is attention."""
    return (operators(tc, "attention") * 2 * kv_heads(tc) * tc["head_width"]
            * _itemsize(tc))


def state_bytes_per_lane(tc: Dict) -> int:
    """The short convolutions' states of one lane, every such layer."""
    return (operators(tc, "conv") * (tc["conv_taps"] - 1) * tc["d_model"]
            * _itemsize(tc))


def decode_step_min_bytes(tc: Dict, live_rows: float,
                          lanes: float = 0.0) -> float:
    """The least a decode step must move: the weights outside the routed
    experts once, the cached rows the live lanes hold, and the states of
    ``lanes`` lanes read and written."""
    return (decode_step_weight_bytes(tc) + kv_bytes_per_row(tc) * live_rows
            + 2 * state_bytes_per_lane(tc) * lanes)


# --- the mechanism's own counts, from what the program's spans say ---------

def conv_pass_bytes(tc: Dict, lanes: float) -> float:
    """One pass over the convolution layers: their weights once, and
    ``lanes`` lanes' states read and written."""
    return (operators(tc, "conv") * conv_weight_count(tc) * _itemsize(tc)
            + 2 * state_bytes_per_lane(tc) * lanes)


def conv_pass_flops(tc: Dict, rows: float) -> float:
    """``rows`` rows through the convolution layers: two operations a
    multiply-add of ``w_in`` and ``w_out``, the taps' multiply-adds and the
    two gates."""
    d = tc["d_model"]
    per_row = 2.0 * (d * 3 * d + d * d) + (2.0 * tc["conv_taps"] + 2.0) * d
    return operators(tc, "conv") * per_row * rows


def conv_min_seconds(tc: Dict, peaks: Dict, lanes: int, passes: int,
                     chunk: int) -> float:
    """The least time the short convolutions of one dispatch can take, from
    its ``kubeshare.engine.conv`` span: ``passes`` decode steps over the
    ``lanes`` lanes less the chunk's, and the chunk of ``chunk`` rows (0:
    none), each the larger of its bytes at the chip's HBM rate and its
    operations at its bf16 peak."""
    hbm, flops = peaks["hbm_bytes_per_s"], peaks["bf16_flops"]

    def one(rows: float, with_state: float) -> float:
        return max(conv_pass_bytes(tc, with_state) / hbm,
                   conv_pass_flops(tc, rows) / flops)

    decode_lanes = lanes - (1 if chunk else 0)
    least = passes * one(decode_lanes, decode_lanes) if decode_lanes else 0.0
    return least + (one(chunk, 1) if chunk else 0.0)
