"""Seeded bf16 weights of ``configs/longcat-flash-chat.json``, made on the
device in the pytree ``transformer_init`` gives the ``latent_shortcut``
block: per double layer two latent attentions (``wdq``, ``q_norm``, ``wuq``,
``wdkv``, ``kv_norm``, ``wukv``, ``wo``), four norms, two gated FFNs
(``w_gate``, ``w_up``, ``w_down``) and the expert layer's router over every
output and the ``experts_held`` experts this rank holds.

One jitted call makes one matrix in the served dtype — drawn in float32 and
rounded once, so the program and the reference, which upcasts these very
arrays, see identical values — and nothing the size of a layer is ever held
in float32: at the cell's size 10.35 GB of bf16 are made beside at most one
float32 matrix of 0.8 GB (a layer's 16 expert gates).
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


def _layer(key, tc: Dict, dtype) -> Dict:
    d, h, f = tc["d_model"], tc["n_heads"], tc["d_ff"]
    qr, kr = tc["q_lora_rank"], tc["kv_lora_rank"]
    nope, rope, vd = (tc["qk_nope_head_dim"], tc["qk_rope_head_dim"],
                      tc["v_head_dim"])
    held = tc.get("experts_held") or tc["n_routed_experts"]
    fe = tc["expert_d_ff"]
    outputs = tc["n_routed_experts"] + tc["n_zero_experts"]
    keys = iter(jax.random.split(key, 20))
    make = lambda shape, fan_in: _normal(next(keys), shape, fan_in, dtype)
    ones = lambda n: {"scale": jnp.ones((n,), dtype)}

    def attn():
        return {"wdq": make((d, qr), d), "q_norm": ones(qr),
                "wuq": make((qr, h, nope + rope), qr),
                "wdkv": make((d, kr + rope), d), "kv_norm": ones(kr),
                "wukv": make((kr, h, nope + vd), kr),
                "wo": make((h, vd, d), h * vd)}

    def ffn():
        return {"w_gate": make((d, f), d), "w_up": make((d, f), d),
                "w_down": make((f, d), f)}

    return {"attn": [attn(), attn()],
            "norm_attn": [ones(d), ones(d)], "norm_ffn": [ones(d), ones(d)],
            "ffn": [ffn(), ffn()],
            "moe": {"router": make((d, outputs), d),
                    "w_gate": make((held, d, fe), d),
                    "w_up": make((held, d, fe), d),
                    "w_down": make((held, fe, d), fe)}}


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
