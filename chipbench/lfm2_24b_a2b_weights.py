"""Seeded bf16 weights of ``configs/lfm2-24b-a2b.json``, made on the device
in the pytree ``transformer_init`` gives the ``gqa_moe`` block where its
layers name their operator: per layer two norms, the OPERATOR — a gated short
convolution (``conv``: ``w_in`` [d, 3 d] whose thirds are B, C and u in that
order, ``filter`` [taps, d] with tap j weighing the row ``taps - 1 - j`` back,
``w_out`` [d, d]) or grouped-query attention (``attn``: ``wq`` [d, H x hd],
``wk`` and ``wv`` [d, K x hd] held as matrices, ``wo`` [H, hd, d], the two
per-head norms) — and ONE feed-forward: a dense gated FFN (``w_gate``,
``w_up``, ``w_down``) in the leading ``first_dense_layers`` layers, in the
rest the router over every expert with its choice bias and all the routed
experts.

Every matrix is normal(0, 1/fan_in) (the filter's fan-in is its taps), drawn
in float32 and rounded once to the served dtype, so the program and the
reference, which upcasts these very arrays, see identical values.  The choice
bias is normal(0, 0.02): seeded, not trained.  Beside sigmoid scores that
spread by 0.21 it changes the four chosen in 47% of the rows, so a program
which weighed by the biased score, or chose without the bias, would fail the
comparison; at ``joyai_llm_flash_weights``' 0.05 (82% of the rows) it also
made a seed's experts unevenly popular, 44 of 64 touched by 23 lanes where a
balanced router touches 49.5, and that count moved by 0.85% from seed to seed
(0.25% here; iid rows through seeded routers, PERF.md section 6, PR 43) —
four tenths of a percent of ``tokens_per_s`` that was the seed's and no
program's, and more imbalance than a bias trained to level the load leaves.  One
jitted call makes one matrix, and the experts' matrices an expert at a time
inside it, so nothing larger than one dense FFN matrix (96 MB in float32) is
held in float32 beside the 8.3 GB of bf16.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.joyai_llm_flash_weights import _experts, _normal

BIAS_STD = 0.02


def _layer(key, tc: Dict, dtype, operator: str, dense: bool) -> Dict:
    d, h, hd = tc["d_model"], tc["n_heads"], tc["head_width"]
    h_kv = tc.get("n_kv_heads") or h
    experts, fe = tc["n_routed_experts"], tc["expert_d_ff"]
    keys = iter(jax.random.split(key, 16))
    make = lambda shape, fan_in: _normal(next(keys), shape,
                                         (1.0 / fan_in) ** 0.5, dtype)
    ones = lambda n: {"scale": jnp.ones((n,), dtype)}
    layer = {"norm1": ones(d), "norm2": ones(d)}
    if operator == "conv":
        taps = tc["conv_taps"]
        layer["conv"] = {"w_in": make((d, 3 * d), d),
                         "filter": make((taps, d), taps),
                         "w_out": make((d, d), d)}
    else:
        layer["attn"] = {"wq": make((d, h * hd), d),
                         "wk": make((d, h_kv * hd), d),
                         "wv": make((d, h_kv * hd), d),
                         "wo": make((h, hd, d), h * hd),
                         "q_norm": ones(hd), "k_norm": ones(hd)}
    if dense:
        f = tc["d_ff"]
        layer["ffn"] = {"w_gate": make((d, f), d), "w_up": make((d, f), d),
                        "w_down": make((f, d), f)}
        return layer
    layer["moe"] = {"router": make((d, experts), d),
                    "w_gate": _experts(next(keys), (experts, d, fe), d, dtype),
                    "w_up": _experts(next(keys), (experts, d, fe), d, dtype),
                    "w_down": _experts(next(keys), (experts, fe, d), fe,
                                       dtype),
                    "bias": _normal(next(keys), (experts,), BIAS_STD, dtype)}
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
            "layers": [_layer(keys[2 + i], tc, dtype, operator,
                              i < tc.get("first_dense_layers", 0))
                       for i, operator in enumerate(tc["layer_operators"])],
            "final_norm": {"scale": jnp.ones((d,), dtype)},
            "lm_head": _normal(keys[1], (d, vocab), std, dtype)}
