"""The count of bytes of ``configs/joyai-llm-flash.json``: what one token
caches, and the least a decode step must read.

The cache row is the latent one: ``kv_lora_rank`` values a layer in the pool's
K array and ``qk_rope_head_dim`` in its V array, whose rows hold two layers'
rotary keys side by side — so an odd count of layers leaves a spare half row,
which the pool holds and ``kv_bytes_per_row`` counts (5,888 B a token at the
cell's size: 5 x 512 + 3 x 128 values), and a step never reads
(``kv_read_bytes_per_row``: 5,760 B).  A decode step must read everything
outside the routed experts — every layer's latent attention and two norms,
the leading layer's dense FFN, every expert layer's router with its bias and
its shared expert, the final norm and the head — and the live lanes' latent
rows, **and no routed expert**: which of them a step reads is its routing's,
not the shapes'.  So a share of a roofline worked out from
``decode_step_min_bytes`` reads low and never over 100%; ``expert_bytes`` is
what each touched expert adds, for a reader that knows how many were
(``step.mixed_routed_hbm_roofline.backlog``,
``step.mixed_expert_bytes_share.backlog``).
"""

from __future__ import annotations

from typing import Dict

from chipbench.roofline import _itemsize


def attention_weight_count(tc: Dict) -> int:
    d, h = tc["d_model"], tc["n_heads"]
    qr, kr = tc["q_lora_rank"], tc["kv_lora_rank"]
    nope, rope, vd = (tc["qk_nope_head_dim"], tc["qk_rope_head_dim"],
                      tc["v_head_dim"])
    return (d * qr + qr + qr * h * (nope + rope) + d * (kr + rope) + kr
            + kr * h * (nope + vd) + h * vd * d)


def expert_layers(tc: Dict) -> int:
    return tc["n_layers"] - tc.get("first_dense_layers", 0)


def outside_experts_count(tc: Dict) -> int:
    """Every layer without its routed experts."""
    d, fe = tc["d_model"], tc["expert_d_ff"]
    outputs = tc["n_routed_experts"] + tc.get("n_zero_experts", 0)
    bias = outputs if tc.get("router_choice_bias") else 0
    routed = expert_layers(tc)
    return (tc["n_layers"] * (attention_weight_count(tc) + 2 * d)
            + tc.get("first_dense_layers", 0) * 3 * d * tc["d_ff"]
            + routed * (d * outputs + bias
                        + tc.get("n_shared_experts", 0) * 3 * d * fe))


def expert_bytes(tc: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * tc["d_model"] * tc["expert_d_ff"] * _itemsize(tc)


def decode_step_weight_bytes(tc: Dict) -> int:
    count = outside_experts_count(tc)
    count += tc["d_model"] + tc["d_model"] * tc["vocab_size"]
    return count * _itemsize(tc)


def kv_bytes_per_row(tc: Dict) -> int:
    """What the pool holds a token: a latent row a layer, and a row of two
    rotary keys every two layers (the last one half spare at an odd count)."""
    packed_rows = -(-tc["n_layers"] // 2)
    return (tc["n_layers"] * tc["kv_lora_rank"]
            + packed_rows * 2 * tc["qk_rope_head_dim"]) * _itemsize(tc)


def kv_read_bytes_per_row(tc: Dict) -> int:
    """What a step reads of a cached token: no spare half row."""
    return (tc["n_layers"] * (tc["kv_lora_rank"] + tc["qk_rope_head_dim"])
            * _itemsize(tc))


def decode_step_min_bytes(tc: Dict, live_rows: float) -> float:
    return decode_step_weight_bytes(tc) + kv_read_bytes_per_row(tc) * live_rows
