"""Operations and bytes computed from shapes, and the table of peaks."""

from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       f"chipbench/peaks.json with its source")
    return table[device_kind]


def _itemsize(tc: Dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[tc["dtype"]]


def layer_weight_count(tc: Dict) -> int:
    d, h, f = tc["d_model"], tc["n_heads"], tc["d_ff"]
    h_kv = tc.get("n_kv_heads") or h
    hd = d // h
    return 2 * d * h * hd + 2 * d * h_kv * hd + 2 * d * f + 2 * d


def decode_step_weight_bytes(tc: Dict) -> int:
    """Weight bytes one decode step must read: every layer and the output
    head once; of the embedding and position tables only the rows of the
    lanes, which are counted as nothing."""
    count = tc["n_layers"] * layer_weight_count(tc)
    count += tc["d_model"] + tc["d_model"] * tc["vocab_size"]
    return count * _itemsize(tc)


def kv_bytes_per_row(tc: Dict) -> int:
    h_kv = tc.get("n_kv_heads") or tc["n_heads"]
    hd = tc["d_model"] // tc["n_heads"]
    return 2 * tc["n_layers"] * h_kv * hd * _itemsize(tc)


def decode_step_min_bytes(tc: Dict, live_rows: float) -> float:
    """The least a decode step must move: the weights once and the KV rows
    the live lanes hold (``live_rows``, summed over lanes) — not the
    ``max_request_len`` view the program gathers."""
    return decode_step_weight_bytes(tc) + kv_bytes_per_row(tc) * live_rows


def matmul_chain_flops(n: int, chain: int) -> float:
    """Pod B's step: ``chain`` products of two n x n matrices."""
    return 2.0 * n ** 3 * chain
