"""Seeded bf16 weights of ``configs/smallthinker-21ba3b-instruct.json``, made
on the device in the pytree ``transformer_init`` gives the ``gqa_moe`` block
where its layers name their operator and the model has no q/k norm: per layer
two norms, grouped-query attention (``attn``: ``wq`` [d, H x hd], ``wk`` and
``wv`` [d, K x hd] held as matrices, ``wo`` [H, hd, d]; no per-head norm, no
bias — a "global" and a "window" layer hold the same arrays, what differs is
the mask and the rotation) and the router over every expert with all the
routed experts (``w_gate`` / ``w_up`` [experts, d, f], ``w_down`` [experts, f,
d]; no choice bias, no shared expert, no dense layer).

Every matrix is normal(0, 1/fan_in), drawn in float32 and rounded once to the
served dtype, so the program and the reference, which upcasts these very
arrays, see identical values; norm gains are 1.  One jitted call makes one
matrix, and the experts' matrices an expert at a time inside it, so nothing
larger than the embedding (1.56 GB in float32) is held in float32 beside the
7.9 GB of bf16.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.sdar_30b_a3b_chat_weights import _experts, _normal


def _layer(key, tc: Dict, dtype) -> Dict:
    d, h, hd = tc["d_model"], tc["n_heads"], tc["head_width"]
    h_kv = tc.get("n_kv_heads") or h
    experts, fe = tc["n_routed_experts"], tc["expert_d_ff"]
    keys = iter(jax.random.split(key, 8))
    ones = lambda n: {"scale": jnp.ones((n,), dtype)}
    return {"norm1": ones(d), "norm2": ones(d),
            "attn": {"wq": _normal(next(keys), (d, h * hd), d, dtype),
                     "wk": _normal(next(keys), (d, h_kv * hd), d, dtype),
                     "wv": _normal(next(keys), (d, h_kv * hd), d, dtype),
                     "wo": _normal(next(keys), (h, hd, d), h * hd, dtype)},
            "moe": {"router": _normal(next(keys), (d, experts), d, dtype),
                    "w_gate": _experts(next(keys), (experts, d, fe), d,
                                       dtype),
                    "w_up": _experts(next(keys), (experts, d, fe), d, dtype),
                    "w_down": _experts(next(keys), (experts, fe, d), fe,
                                       dtype)}}


def make_weights(seed: int, tc: Dict) -> Dict:
    """``tc``: the configuration file's ``transformer_config`` group."""
    dtype = jnp.dtype(tc["dtype"])
    d, vocab = tc["d_model"], tc["vocab_size"]
    # --seed may need more than 32 signed bits: fold the high part in
    root = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)
    keys = jax.random.split(root, 2 + tc["n_layers"])
    return {"embed": _normal(keys[0], (vocab, d), d, dtype),
            "layers": [_layer(keys[2 + i], tc, dtype)
                       for i in range(tc["n_layers"])],
            "final_norm": {"scale": jnp.ones((d,), dtype)},
            "lm_head": _normal(keys[1], (d, vocab), d, dtype)}
