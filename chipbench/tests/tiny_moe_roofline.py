"""The second block's count of bytes (``configs/tiny_moe.json``).  It caches
what the dense block caches, a K and a V row a KV head a layer, so
``kv_bytes_per_row`` is ``chipbench/roofline.py``'s; a decode step's weights
follow the routing: a routed layer must read its router and the ``top_k``
experts one token chose, not every expert.  That is the least any step can
read (more lanes may choose more experts, at most all of them), so a share
of a roofline worked out from it can only come out low, never over 100%.
"""

from __future__ import annotations

from typing import Dict

from chipbench.roofline import _itemsize, kv_bytes_per_row


def layer_weight_count(tc: Dict, routed: bool) -> int:
    d, h, f = tc["d_model"], tc["n_heads"], tc["d_ff"]
    h_kv = tc.get("n_kv_heads") or h
    hd = d // h
    attention = 2 * d * h * hd + 2 * d * h_kv * hd + 2 * d
    if routed:
        return attention + d * tc["moe_num_experts"] \
            + tc["moe_top_k"] * 2 * d * f
    return attention + 2 * d * f


def decode_step_weight_bytes(tc: Dict) -> int:
    """Every layer's attention and norms, a dense layer's two matrices, a
    routed layer's router and ``top_k`` experts, the final norm and the
    output head once."""
    every = tc["moe_every"]
    count = sum(layer_weight_count(tc, i % every == every - 1)
                for i in range(tc["n_layers"]))
    count += tc["d_model"] + tc["d_model"] * tc["vocab_size"]
    return count * _itemsize(tc)


def decode_step_min_bytes(tc: Dict, live_rows: float) -> float:
    return decode_step_weight_bytes(tc) + kv_bytes_per_row(tc) * live_rows
