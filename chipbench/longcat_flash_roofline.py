"""The count of bytes of ``configs/longcat-flash-chat.json``: what one
token caches, and the least a decode step must read.

The cache row is the latent one: ``kv_lora_rank + qk_rope_head_dim`` values
an attention sub-layer, two sub-layers a layer, no heads (9,216 B a token at
the cell's size).  A decode step must read everything outside the experts —
both latent attentions, both gated FFNs, the norms and the router of every
layer, the final norm and the head — and the live lanes' latent rows, **and
no expert**: a step whose rows chose no expert held here reads none.  So a
share of a roofline worked out from ``decode_step_min_bytes`` reads low and
never over 100%; ``expert_bytes`` is what each touched expert adds, for a
reader that knows how many were (``step.mixed_routed_hbm_roofline.backlog``).
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


def layer_weight_count(tc: Dict) -> int:
    """One double layer without its experts."""
    d = tc["d_model"]
    outputs = tc["n_routed_experts"] + tc["n_zero_experts"]
    return (2 * attention_weight_count(tc) + 2 * 3 * d * tc["d_ff"]
            + 4 * d + d * outputs)


def expert_bytes(tc: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * tc["d_model"] * tc["expert_d_ff"] * _itemsize(tc)


def decode_step_weight_bytes(tc: Dict) -> int:
    count = tc["n_layers"] * layer_weight_count(tc)
    count += tc["d_model"] + tc["d_model"] * tc["vocab_size"]
    return count * _itemsize(tc)


def kv_bytes_per_row(tc: Dict) -> int:
    return (2 * tc["n_layers"]
            * (tc["kv_lora_rank"] + tc["qk_rope_head_dim"]) * _itemsize(tc))


def decode_step_min_bytes(tc: Dict, live_rows: float) -> float:
    return decode_step_weight_bytes(tc) + kv_bytes_per_row(tc) * live_rows
