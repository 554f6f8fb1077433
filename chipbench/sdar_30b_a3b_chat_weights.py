"""Seeded bf16 weights of ``configs/sdar-30b-a3b-chat.json``, made on the
device in the pytree ``transformer_init`` gives the ``gqa_moe`` block: per
layer one attention (``wq`` [d, H, hd], ``wk`` / ``wv`` [d, K, hd], ``wo``
[H, hd, d], and the two per-head norms ``q_norm`` / ``k_norm`` [hd]), two
norms, and the routed experts, all held:
the router [d, experts] and ``w_gate`` / ``w_up`` [experts, d, f], ``w_down``
[experts, f, d].  No dense MLP, no shared expert, no choice bias.

Every matrix is normal(0, 1/fan_in), drawn in float32 and rounded once to
the served dtype, so the program and the reference, which upcasts these very
arrays, see identical values; norm gains are 1.  One jitted call makes one
matrix, and the experts' matrices an expert at a time inside it: a layer's
128 gates are 0.8 GB in float32, so nothing larger than one expert's matrix
(6.3 MB) is ever held in float32 beside the 8.7 GB of bf16.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / fan_in) ** 0.5).astype(dtype)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _experts(key, shape, fan_in, dtype):
    """[experts, *shape[1:]], an expert at a time."""
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape[1:], jnp.float32)
                   * (1.0 / fan_in) ** 0.5).astype(dtype),
        jax.random.split(key, shape[0]))


def _layer(key, tc: Dict, dtype) -> Dict:
    d, h = tc["d_model"], tc["n_heads"]
    h_kv = tc.get("n_kv_heads") or h
    hd = tc.get("head_width") or d // h
    experts, fe = tc["n_routed_experts"], tc["expert_d_ff"]
    keys = iter(jax.random.split(key, 8))
    ones = lambda n: {"scale": jnp.ones((n,), dtype)}
    attn = {"wq": _normal(next(keys), (d, h, hd), d, dtype),
            "wk": _normal(next(keys), (d, h_kv, hd), d, dtype),
            "wv": _normal(next(keys), (d, h_kv, hd), d, dtype),
            "wo": _normal(next(keys), (h, hd, d), h * hd, dtype),
            "q_norm": ones(hd), "k_norm": ones(hd)}
    return {"attn": attn, "norm1": ones(d), "norm2": ones(d),
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
