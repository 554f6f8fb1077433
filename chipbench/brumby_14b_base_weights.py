"""Seeded bf16 weights of ``configs/brumby-14b-base.json``, made on the
device in the pytree ``transformer_init`` gives the ``retention`` block: per
layer one attention (``wq`` [d, H x hd], ``wk`` / ``wv`` [d, K x hd] as
matrices, ``wo`` [H, hd, d], the two per-head norms ``q_norm`` / ``k_norm`` [hd] and the
gate's map ``gate.w`` [d, K] with its bias ``gate.b`` [K]), two norms, and a
dense SwiGLU (``w_gate`` / ``w_up`` [d, f], ``w_down`` [f, d]).

Every matrix is normal(0, 1/fan_in), drawn in float32 and rounded once to
the served dtype, so the program and the reference, which upcasts these very
arrays, see identical values; norm gains are 1.  One jitted call makes one
matrix, so nothing larger than one matrix in float32 (356 MB: a SwiGLU
matrix) is held beside the bf16 weights.

**The seeded gate.**  With ``gate.w`` normal(0, 1/d) and no bias a row would
keep ``sigmoid(N(0, 1))`` of everything before it — about a half: a memory
of a few rows, a state that nothing reads and that ``correct`` could not
see.  A trained model of this family remembers across its whole context, so
the seeded gate is set to: the logit of a row is ``GATE_BIAS + N(0,
GATE_SPREAD^2)`` (``gate.w`` normal(0, GATE_SPREAD^2 / d), float32 ``gate.b``
= GATE_BIAS), with GATE_BIAS = log(4096) + GATE_SPREAD^2 / 2, so that the
mean of ``a_t = logsigmoid(logit)`` is about ``-E[e^-logit] = -1/4096`` a
row: a state folded 8,000 rows ago still carries e^-2 of its weight, and
the gate still depends on its input (a row's ``a`` spreads from about
-1/11,000 to -1/1,500).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

GATE_SPREAD = 1.0
GATE_BIAS = math.log(4096.0) + GATE_SPREAD ** 2 / 2.0


@partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, variance, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * variance ** 0.5).astype(dtype)


def _layer(key, tc: Dict, dtype) -> Dict:
    d, h, f = tc["d_model"], tc["n_heads"], tc["d_ff"]
    h_kv = tc.get("n_kv_heads") or h
    hd = tc.get("head_width") or d // h
    keys = iter(jax.random.split(key, 8))
    ones = lambda n: {"scale": jnp.ones((n,), dtype)}
    attn = {"wq": _normal(next(keys), (d, h * hd), 1.0 / d, dtype),
            "wk": _normal(next(keys), (d, h_kv * hd), 1.0 / d, dtype),
            "wv": _normal(next(keys), (d, h_kv * hd), 1.0 / d, dtype),
            "wo": _normal(next(keys), (h, hd, d), 1.0 / (h * hd), dtype),
            "q_norm": ones(hd), "k_norm": ones(hd),
            "gate": {"w": _normal(next(keys), (d, h_kv),
                                  GATE_SPREAD ** 2 / d, dtype),
                     "b": jnp.full((h_kv,), GATE_BIAS, jnp.float32)}}
    return {"attn": attn, "norm1": ones(d), "norm2": ones(d),
            "ffn": {"w_gate": _normal(next(keys), (d, f), 1.0 / d, dtype),
                    "w_up": _normal(next(keys), (d, f), 1.0 / d, dtype),
                    "w_down": _normal(next(keys), (f, d), 1.0 / f, dtype)}}


def make_weights(seed: int, tc: Dict) -> Dict:
    """``tc``: the configuration file's ``transformer_config`` group."""
    dtype = jnp.dtype(tc["dtype"])
    d, vocab = tc["d_model"], tc["vocab_size"]
    # --seed may need more than 32 signed bits: fold the high part in
    root = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)
    keys = jax.random.split(root, 2 + tc["n_layers"])
    return {"embed": _normal(keys[0], (vocab, d), 1.0 / d, dtype),
            "layers": [_layer(keys[2 + i], tc, dtype)
                       for i in range(tc["n_layers"])],
            "final_norm": {"scale": jnp.ones((d,), dtype)},
            "lm_head": _normal(keys[1], (d, vocab), 1.0 / d, dtype)}
