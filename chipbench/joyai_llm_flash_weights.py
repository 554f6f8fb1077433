"""Seeded bf16 weights of ``configs/joyai-llm-flash.json``, made on the
device in the pytree ``transformer_init`` gives the ``latent_moe`` block: per
layer one latent attention (``wdq``, ``q_norm``, ``wuq``, ``wdkv``,
``kv_norm``, ``wukv``, ``wo``), two norms and ONE feed-forward — a dense
gated FFN (``w_gate``, ``w_up``, ``w_down``) in the leading
``first_dense_layers`` layers; in the rest the router over every output with
its choice bias, the ``experts_held`` routed experts this chip holds (all of
them at the cell's size) and the shared expert.

Every matrix is normal(0, 1/fan_in), drawn in float32 and rounded once to
the served dtype, so the program and the reference, which upcasts these very
arrays, see identical values.  The choice bias is normal(0, 0.05) — seeded,
not trained: wide enough beside sigmoid scores that a program which WEIGHED
by the biased score, or chose without the bias, would fail the comparison.
One jitted call makes one matrix, and the experts' matrices an expert at a
time inside it: a layer's 256 gates are 1.6 GB in float32, so nothing larger
than one expert's matrix (6.3 MB) is ever held in float32 beside the 11.1 GB
of bf16.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

BIAS_STD = 0.05


@partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _experts(key, shape, fan_in, dtype):
    """[experts, *shape[1:]], an expert at a time."""
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape[1:], jnp.float32)
                   * (1.0 / fan_in) ** 0.5).astype(dtype),
        jax.random.split(key, shape[0]))


def _layer(key, tc: Dict, dtype, dense: bool) -> Dict:
    d, h = tc["d_model"], tc["n_heads"]
    qr, kr = tc["q_lora_rank"], tc["kv_lora_rank"]
    nope, rope, vd = (tc["qk_nope_head_dim"], tc["qk_rope_head_dim"],
                      tc["v_head_dim"])
    held = tc.get("experts_held") or tc["n_routed_experts"]
    fe = tc["expert_d_ff"]
    outputs = tc["n_routed_experts"] + tc.get("n_zero_experts", 0)
    keys = iter(jax.random.split(key, 16))
    make = lambda shape, fan_in: _normal(next(keys), shape,
                                         (1.0 / fan_in) ** 0.5, dtype)
    ones = lambda n: {"scale": jnp.ones((n,), dtype)}

    def ffn(width):
        return {"w_gate": make((d, width), d), "w_up": make((d, width), d),
                "w_down": make((width, d), width)}

    layer = {"attn": {"wdq": make((d, qr), d), "q_norm": ones(qr),
                      "wuq": make((qr, h, nope + rope), qr),
                      "wdkv": make((d, kr + rope), d), "kv_norm": ones(kr),
                      "wukv": make((kr, h, nope + vd), kr),
                      "wo": make((h, vd, d), h * vd)},
             "norm_attn": ones(d), "norm_ffn": ones(d)}
    if dense:
        layer["ffn"] = ffn(tc["d_ff"])
        return layer
    layer["moe"] = {"router": make((d, outputs), d),
                    "w_gate": _experts(next(keys), (held, d, fe), d, dtype),
                    "w_up": _experts(next(keys), (held, d, fe), d, dtype),
                    "w_down": _experts(next(keys), (held, fe, d), fe, dtype)}
    if tc.get("router_choice_bias"):
        layer["moe"]["bias"] = _normal(next(keys), (outputs,), BIAS_STD,
                                       dtype)
    if tc.get("n_shared_experts"):
        layer["shared"] = ffn(tc["n_shared_experts"] * fe)
    return layer


def make_weights(seed: int, tc: Dict) -> Dict:
    """``tc``: the configuration file's ``transformer_config`` group."""
    dtype = jnp.dtype(tc["dtype"])
    d, vocab = tc["d_model"], tc["vocab_size"]
    # --seed may need more than 32 signed bits: fold the high part in
    root = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)
    keys = jax.random.split(root, 2 + tc["n_layers"])
    std = (1.0 / d) ** 0.5
    return {"embed": _normal(keys[0], (vocab, d), std, dtype),
            "layers": [_layer(keys[2 + i], tc, dtype,
                              i < tc.get("first_dense_layers", 0))
                       for i in range(tc["n_layers"])],
            "final_norm": {"scale": jnp.ones((d,), dtype)},
            "lm_head": _normal(keys[1], (d, vocab), std, dtype)}
