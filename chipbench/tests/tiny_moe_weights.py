"""Seeded bf16 weights of the second block (``configs/tiny_moe.json``): the
repository's block with a routed-expert feed-forward in every
``moe_every``-th layer — a pytree ``chipbench/weights.py`` cannot make.  One
jitted call makes every leaf on the device in the served dtype, from the
seed; the layout is the one ``transformer_init`` gives a layer for which
``TransformerConfig.layer_is_moe`` holds (``"moe"``: ``router`` [d, e],
``w_in`` [e, d, f], ``w_out`` [e, f, d], in place of ``"mlp"``).
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.weights import _normal


@partial(jax.jit, static_argnums=(1,))
def _all(root, sizes):
    tc = dict(sizes)
    dtype = jnp.dtype(tc["dtype"])
    d, h, f, vocab = tc["d_model"], tc["n_heads"], tc["d_ff"], tc["vocab_size"]
    h_kv, hd, e = tc.get("n_kv_heads") or h, d // h, tc["moe_num_experts"]
    keys = jax.random.split(root, 3 + tc["n_layers"])
    layers = []
    for i in range(tc["n_layers"]):
        k = jax.random.split(keys[3 + i], 7)
        layer = {
            "attn": {"wq": _normal(k[0], (d, h, hd), d, dtype),
                     "wk": _normal(k[1], (d, h_kv, hd), d, dtype),
                     "wv": _normal(k[2], (d, h_kv, hd), d, dtype),
                     "wo": _normal(k[3], (h, hd, d), d, dtype)},
            "norm1": {"scale": jnp.ones((d,), dtype)},
            "norm2": {"scale": jnp.ones((d,), dtype)},
        }
        if i % tc["moe_every"] == tc["moe_every"] - 1:
            layer["moe"] = {"router": _normal(k[4], (d, e), d, dtype),
                            "w_in": _normal(k[5], (e, d, f), d, dtype),
                            "w_out": _normal(k[6], (e, f, d), f, dtype)}
        else:
            layer["mlp"] = {"w_in": _normal(k[5], (d, f), d, dtype),
                            "w_out": _normal(k[6], (f, d), f, dtype)}
        layers.append(layer)
    params = {"embed": _normal(keys[0], (vocab, d), d, dtype),
              "layers": layers,
              "final_norm": {"scale": jnp.ones((d,), dtype)},
              "lm_head": _normal(keys[1], (d, vocab), d, dtype)}
    if tc["positional"] == "learned":
        params["pos_embed"] = _normal(keys[2], (tc["max_seq_len"], d), d,
                                      dtype)
    return params


def make_weights(seed: int, tc: Dict) -> Dict:
    """``tc``: the configuration file's ``transformer_config`` group."""
    # --seed may need more than 32 signed bits: fold the high part in
    root = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)
    return _all(root, tuple(sorted(tc.items())))
