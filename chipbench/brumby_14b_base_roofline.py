"""The count of bytes and operations of ``configs/brumby-14b-base.json``:
what one token caches in the paged pool, what a lane's recurrent state
holds, and the least a step must read.

**The pool** holds a lane's UNFOLDED rows only: a K and a V of
``head_width`` values a KV head a layer in the served dtype, and the row's
log gate, one float32 a KV head a layer (20,480 + 160 = 20,640 B a token at
the cell's size: 5 layers x 2 x 8 heads x 128 x 2 B, and 5 x 8 x 4 B).
``kv_bytes_per_row`` is exactly what ``engine.pool``'s three arrays hold a
row; the harness sizes the pool by it and fails set-up otherwise.

**The states** live beside the pool, by slot: float32 ``[state_rows,
phi_width]`` a KV head a layer — ``head_width + 1`` rows that mean something
(the values' columns of S and the sum of keys z) in ``state_rows`` = that
rounded up to 8 (136 at 128: whole tiles as stored), ``phi_width =
(head_width / 2 + 1) x head_width`` columns (8,320 at 128: the 8,256 features
of ``phi`` laid out by diagonals, 64 columns of zeros among them — the width
the TPU's tiles pad 8,256 to anyway).  ``state_bytes_per_lane`` is what one
lane's state HOLDS over all layers (181.0 MB here); ``state_read_bytes``
counts the 129 rows a query must read (171.7 MB a lane).

**A decode step** must read every layer's weights and the head once, each
live lane's unfolded rows, and the state of every lane that has folded a
key block: ``decode_step_min_bytes(tc, live_rows, state_lanes)``.  The
harness's own callers pass the live rows alone (``state_lanes`` 0), and the
rows THEY count are the requests' whole lengths, not the tails: so
``step.mixed_hbm_roofline.backlog`` is not listed for this cell, and
``step.retention_hbm_roofline.backlog`` reads the mechanism's own bytes
from the program's spans (``retention_min_bytes``).
"""

from __future__ import annotations

from typing import Dict

from chipbench.roofline import _itemsize

GATE_ITEMSIZE = 4  # the log gate is float32 whatever the rows are
STATE_ITEMSIZE = 4


def head_width(tc: Dict) -> int:
    return tc.get("head_width") or tc["d_model"] // tc["n_heads"]


def kv_heads(tc: Dict) -> int:
    return tc.get("n_kv_heads") or tc["n_heads"]


def phi_width(tc: Dict) -> int:
    hd = head_width(tc)
    return (hd // 2 + 1) * hd


def phi_features(tc: Dict) -> int:
    """The features that are not structurally zero: hd (hd + 1) / 2."""
    hd = head_width(tc)
    return hd * (hd + 1) // 2


def attention_weight_count(tc: Dict) -> int:
    d, hd, k = tc["d_model"], head_width(tc), kv_heads(tc)
    return (2 * d * tc["n_heads"] * hd + 2 * d * k * hd + 2 * hd
            + d * k + k)


def layer_weight_count(tc: Dict) -> int:
    d = tc["d_model"]
    return attention_weight_count(tc) + 3 * d * tc["d_ff"] + 2 * d


def decode_step_weight_bytes(tc: Dict) -> int:
    count = tc["n_layers"] * layer_weight_count(tc)
    count += tc["d_model"] + tc["d_model"] * tc["vocab_size"]
    return count * _itemsize(tc)


def kv_bytes_per_row(tc: Dict) -> int:
    """What the paged pool holds a token: K, V and the log gate."""
    k = kv_heads(tc)
    return tc["n_layers"] * k * (2 * head_width(tc) * _itemsize(tc)
                                 + GATE_ITEMSIZE)


def state_rows(tc: Dict) -> int:
    """Rows of a stored state: head_width + 1 rounded up to whole tiles."""
    return -(-(head_width(tc) + 1) // 8) * 8


def state_bytes_per_lane(tc: Dict) -> int:
    """What one lane's recurrent state holds, all layers."""
    return (tc["n_layers"] * kv_heads(tc) * state_rows(tc) * phi_width(tc)
            * STATE_ITEMSIZE)


def state_need_bytes_per_lane(tc: Dict) -> int:
    """What a query of it must read: the head_width + 1 rows that mean
    something."""
    return (tc["n_layers"] * kv_heads(tc) * (head_width(tc) + 1)
            * phi_width(tc) * STATE_ITEMSIZE)


def decode_step_min_bytes(tc: Dict, live_rows: float,
                          state_lanes: float = 0.0) -> float:
    """The least a decode step must move: the weights once, the unfolded
    rows the live lanes hold and the states of the lanes that have one."""
    return (decode_step_weight_bytes(tc) + kv_bytes_per_row(tc) * live_rows
            + state_need_bytes_per_lane(tc) * state_lanes)


# --- the mechanism's own counts, from what the program's spans say ---------

def state_read_bytes(tc: Dict, state_reads: float) -> float:
    """``state_reads`` lane-passes each read one lane's state."""
    return state_need_bytes_per_lane(tc) * state_reads


def state_read_flops(tc: Dict, state_reads: float, rows: float = 1.0
                     ) -> float:
    """``phi(q)^T [S | z]``: 2 x (hd + 1) x features a query head a row."""
    return (2.0 * (head_width(tc) + 1) * phi_features(tc) * tc["n_heads"]
            * tc["n_layers"] * rows * state_reads)


def tail_read_bytes(tc: Dict, tail_rows: float) -> float:
    """Unfolded rows read as keys and values, with their gates."""
    return kv_bytes_per_row(tc) * tail_rows


def tail_read_flops(tc: Dict, tail_rows: float, rows: float = 1.0) -> float:
    """Scores and weighted values: 4 x hd a query head a key a row."""
    return (4.0 * head_width(tc) * tc["n_heads"] * tc["n_layers"] * rows
            * tail_rows)


def fold_bytes(tc: Dict, folds: float, key_block: int = 512) -> float:
    """A fold reads a lane's state and writes it, and reads the key
    block's rows."""
    return folds * (2 * state_need_bytes_per_lane(tc)
                    + kv_bytes_per_row(tc) * key_block)


def fold_flops(tc: Dict, folds: float, key_block: int = 512) -> float:
    """``sum_j [v_j | 1]^T phi(k_j)``: 2 x (hd + 1) x features a row a KV
    head."""
    return (2.0 * (head_width(tc) + 1) * phi_features(tc) * kv_heads(tc)
            * tc["n_layers"] * key_block * folds)


def retention_min_bytes(tc: Dict, state_reads: float, tail_rows: float,
                        folds: float) -> float:
    """The least the mechanism must move for what a dispatch's
    ``kubeshare.engine.retention`` span says it carried."""
    return (state_read_bytes(tc, state_reads)
            + tail_read_bytes(tc, tail_rows) + fold_bytes(tc, folds))
