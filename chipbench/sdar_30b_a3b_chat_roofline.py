"""The count of bytes of ``configs/sdar-30b-a3b-chat.json``: what one token
caches, and the least a pass must read.

The cache row is the dense block's: a K and a V of ``head_width`` values a KV
head a layer (12,288 B a token at the cell's size: 6 layers x 2 x 4 heads x
128 values).  A pass — one denoising or commit pass over the lanes' blocks,
or a decode step of the same block served one token after another — must read
everything outside the routed experts (every layer's attention with its two
per-head norms, its two norms and its router; the final norm and the head),
the lanes' live K/V rows, **and no routed expert**: which of them a pass
reads is its routing's, not the shapes'.  ``decode_step_min_bytes`` is that
count, so a share of a roofline worked out from it reads low and never over
100%; ``expert_bytes`` is what each touched expert adds, and
``pass_min_bytes`` adds them for a reader that knows how many were touched
(``step.diffusion_routed_hbm_roofline.backlog``).  The embedding's rows a
pass gathers (at most 128 of 151,936) are left out.
"""

from __future__ import annotations

from typing import Dict

from chipbench.roofline import _itemsize


def head_width(tc: Dict) -> int:
    return tc.get("head_width") or tc["d_model"] // tc["n_heads"]


def kv_heads(tc: Dict) -> int:
    return tc.get("n_kv_heads") or tc["n_heads"]


def attention_weight_count(tc: Dict) -> int:
    d, hd = tc["d_model"], head_width(tc)
    return 2 * d * tc["n_heads"] * hd + 2 * d * kv_heads(tc) * hd + 2 * hd


def outside_experts_count(tc: Dict) -> int:
    """Every layer without its routed experts."""
    d = tc["d_model"]
    return tc["n_layers"] * (attention_weight_count(tc) + 2 * d
                             + d * tc["n_routed_experts"])


def expert_bytes(tc: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * tc["d_model"] * tc["expert_d_ff"] * _itemsize(tc)


def decode_step_weight_bytes(tc: Dict) -> int:
    count = outside_experts_count(tc)
    count += tc["d_model"] + tc["d_model"] * tc["vocab_size"]
    return count * _itemsize(tc)


def kv_bytes_per_row(tc: Dict) -> int:
    return (tc["n_layers"] * 2 * kv_heads(tc) * head_width(tc)
            * _itemsize(tc))


def decode_step_min_bytes(tc: Dict, live_rows: float) -> float:
    return decode_step_weight_bytes(tc) + kv_bytes_per_row(tc) * live_rows


def pass_min_bytes(tc: Dict, live_rows: float, touched: float) -> float:
    """The least one pass must read: the weights outside the experts once,
    the three matrices of every (layer, expert) its routing touched, and the
    lanes' live K/V rows."""
    return decode_step_min_bytes(tc, live_rows) + touched * expert_bytes(tc)
