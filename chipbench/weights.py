"""Seeded bf16 weights, made on the device in the pytree
``transformer_init`` would return.

``transformer_init`` makes float32 masters (12.7 GB for starcoder2-3b, which
does not fit beside a pool) and makes them leaf by leaf.  Here one jitted
call per layer shape fills a whole layer in the served dtype, so a 30-layer
model is 32 dispatches of three compiled programs and nothing is ever held
in float32.  The benchmark makes these weights; the program and the plain
reference are both handed the same arrays.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp


def _normal(key, shape, fan_in, dtype):
    # drawn in float32 and rounded once, so the reference (which upcasts
    # these very arrays) and the program see identical values
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / fan_in) ** 0.5).astype(dtype)


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _layer(key, d, h, h_kv, f, dtype):
    hd = d // h
    k = jax.random.split(key, 6)
    return {
        "attn": {
            "wq": _normal(k[0], (d, h, hd), d, dtype),
            "wk": _normal(k[1], (d, h_kv, hd), d, dtype),
            "wv": _normal(k[2], (d, h_kv, hd), d, dtype),
            "wo": _normal(k[3], (h, hd, d), d, dtype),
        },
        "norm1": {"scale": jnp.ones((d,), dtype)},
        "norm2": {"scale": jnp.ones((d,), dtype)},
        "mlp": {
            "w_in": _normal(k[4], (d, f), d, dtype),
            "w_out": _normal(k[5], (f, d), f, dtype),
        },
    }


@partial(jax.jit, static_argnums=(1, 2, 3))
def _table(key, rows, d, dtype):
    return _normal(key, (rows, d), d, dtype)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _head(key, d, vocab, dtype):
    return _normal(key, (d, vocab), d, dtype)


def make_weights(seed: int, tc: Dict) -> Dict:
    """``tc``: the configuration file's ``transformer_config`` group."""
    dtype = jnp.dtype(tc["dtype"])
    d, h, f = tc["d_model"], tc["n_heads"], tc["d_ff"]
    h_kv = tc.get("n_kv_heads") or h
    # --seed may need more than 32 signed bits: fold the high part in
    root = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)
    keys = jax.random.split(root, 3 + tc["n_layers"])
    params = {
        "embed": _table(keys[0], tc["vocab_size"], d, dtype),
        "layers": [_layer(keys[3 + i], d, h, h_kv, f, dtype)
                   for i in range(tc["n_layers"])],
        "final_norm": {"scale": jnp.ones((d,), dtype)},
        "lm_head": _head(keys[1], d, tc["vocab_size"], dtype),
    }
    if tc["positional"] == "learned":
        params["pos_embed"] = _table(keys[2], tc["max_seq_len"], d, dtype)
    return params
