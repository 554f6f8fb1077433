"""The plain reference of ``configs/lfm2-24b-a2b.json``: the first pipeline
stage of LFM2-24B-A2B, written from the equations in straightforward
``jax.numpy``, float32, matmul precision ``highest`` — every row against
every earlier row, no cache, no state carried between chunks, no paging, no
batching, no grouping of rows by expert.  Nothing is imported from the
program.

d = ``d_model``, H query heads and K KV heads of ``head_width`` hd, all linear
maps without bias, rms(x, w) = x . rsqrt(mean x^2 + eps) . w:

    layer l, input x [T, d]:
      y = rms(x, w_op);  x = x + op_l(y);  z = rms(x, w_ffn);  x = x + ffn_l(z)
    op_l, ``layer_operators[l]`` = "conv" (taps L = conv_taps = 3):
      [B | C | u] = y W_in                   (W_in [d, 3 d], the thirds in that order)
      g = B * u
      c_t = sum_j w_j * g_{t - (L - 1) + j}  (depthwise: w_j [d] one value a channel and tap,
                                              zeros before row 0, no bias, no activation)
      op = (C * c) W_out
    op_l, "attention":
      q = y Wq -> [H, hd],  k, v = y Wk, y Wv -> [K, hd]
      q, k = rms over each head's hd values (w [hd]),  then rotate-half rope at rope_theta
      causal softmax(q . k / sqrt(hd)) in float32, a KV head serving H / K query heads;  Wo
    ffn_l, l < first_dense_layers:  (silu(z W1) * (z W3)) W2       (width d_ff)
    ffn_l, else:  s = sigmoid(float32(z) Wr);  T = the top_k of s + b  (the bias chooses, never weighs)
                  w_e = routed_scaling_factor . s_e / (sum_{e' in T} s_e' + 1e-6)
                  sum_{e in T} w_e . (silu(z Wg_e) * (z Wu_e)) Wd_e (width expert_d_ff)
    model:  embed -> layers -> rms(x, w_final) -> untied head

The filter is held ``[L, d]`` (tap-major: a row of ``d`` values a tap), the
release's ``[d, 1, L]`` transposed; tap j weighs the row ``L - 1 - j`` back.
Every expert is run on every row and the rows that did not choose it are
weighted 0 (one ``lax.scan`` over the experts).  It is handed the benchmark's
own seeded bf16 weights, which stay on the device (8.3 GB at the cell's size),
and upcasts them a piece at a time: one operator, one FFN, one expert — never
a layer; the control lowers them the same way.  The pieces that are the same
mathematics elsewhere are those files': grouped-query attention with per-head
norms and rotate-half rope (``sdar_30b_a3b_chat_reference._attend_own`` with
``diffusion_block`` 0: the causal mask), SwiGLU, the embedding and the head
(``longcat_flash_reference``).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import sdar_30b_a3b_chat_reference as gqa
from chipbench.longcat_flash_reference import (  # noqa: F401
    _embed, _head, _normed, _rms_norm, _rows, _swiglu)
from chipbench.reference import CONTROL, _LOW, _f32, _fp8, summarize  # noqa: F401

PAD_TO = gqa.PAD_TO  # sequences are padded to a multiple: few shapes compile
RENORMALISE_EPS = 1e-6
SIZES = ("n_routed_experts", "router_top_k", "routed_scaling_factor",
         "norm_eps")


def _sizes(tc: Dict):
    return tuple((k, tc[k]) for k in SIZES)


@partial(jax.jit, static_argnums=(3, 4))
def _short_conv(x, conv: Dict, norm, eps, fp8_inputs=False):
    """x + ((C * filter(B * u)) W_out) of RMSNorm(x), x [T, d] from row 0."""
    act = _fp8 if fp8_inputs else (lambda a: a)
    d = x.shape[-1]
    with jax.default_matmul_precision("highest"):
        y = act(_rms_norm(x, norm["scale"], eps))
        bcu = y @ _f32(conv["w_in"])
        g = bcu[:, :d] * bcu[:, 2 * d:]
        taps = _f32(conv["filter"])  # [L, d]
        back = taps.shape[0] - 1
        before = jnp.concatenate([jnp.zeros((back, d), jnp.float32), g], 0)
        c = sum(taps[j] * before[j:j + g.shape[0]]
                for j in range(back + 1))
        return x + act(bcu[:, d:2 * d] * c) @ _f32(conv["w_out"])


def _attention(x, attn: Dict, norm, positions, length, tc: Dict,
               fp8_inputs=False):
    """x + attention(RMSNorm(x)): the projections, held as matrices, seen a
    head at a time."""
    d, hd = tc["d_model"], tc["head_width"]
    heads = {name: attn[name].reshape(d, -1, hd)
             for name in ("wq", "wk", "wv")}
    return gqa._attend_own(x, {**attn, **heads}, norm, positions, length,
                           gqa._sizes(tc), fp8_inputs)[0]


def router_weights(z, router, bias, sizes):
    """[T, experts]: w_e where the row chose e, else 0."""
    s = dict(sizes)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(z @ _f32(router))
    _, chosen = jax.lax.top_k(scores + _f32(bias), s["router_top_k"])
    picked = jax.nn.one_hot(chosen, scores.shape[-1]).sum(1) * scores
    return s["routed_scaling_factor"] * picked \
        / (picked.sum(-1, keepdims=True) + RENORMALISE_EPS)


@partial(jax.jit, static_argnums=(3, 4))
def _experts(x, norm, moe: Dict, sizes, low: str = ""):
    """x + sum_{e in T} w_e . FFN_e(RMSNorm(x)), x [T, d]: every expert
    over every row.  ``low`` lowers the router and each expert's matrices,
    a tensor each, and the activation operands."""
    lower = _LOW[low] if low else (lambda w: w)
    act = _fp8 if low == "fp8" else (lambda a: a)
    z = act(_rms_norm(x, norm["scale"], dict(sizes)["norm_eps"]))
    weights = router_weights(z, lower(moe["router"]), moe["bias"], sizes)

    def one(out, expert):
        w_gate, w_up, w_down, weight = expert
        with jax.default_matmul_precision("highest"):
            hidden = jax.nn.silu(z @ _f32(lower(w_gate))) \
                * (z @ _f32(lower(w_up)))
            result = act(hidden) @ _f32(lower(w_down))
        return out + weight[:, None] * result, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(z),
        (moe["w_gate"], moe["w_up"], moe["w_down"], weights.T))
    return x + out


@partial(jax.jit, static_argnums=(1,))
def _lower_group(group: Dict, kind: str) -> Dict:
    """One operator's or one FFN's matrices in the lower precision (norm
    scales stay)."""
    return {k: (v if "norm" in k else _LOW[kind](v))
            for k, v in group.items()}


@partial(jax.jit, static_argnums=(1,))
def lower_precision(layer: Dict, kind: str = CONTROL) -> Dict:
    """One whole layer in the lower precision, for a program that is to
    serve it (the twin's control): every matrix a tensor, each expert's
    too; norm scales and the choice bias stay."""
    low = _LOW[kind]
    out = dict(layer)
    for name in ("conv", "attn", "ffn"):
        if name in layer:
            out[name] = _lower_group(layer[name], kind)
    if "moe" in layer:
        moe = layer["moe"]
        out["moe"] = {**moe, "router": low(moe["router"]),
                      **{k: jax.vmap(low)(moe[k])
                         for k in ("w_gate", "w_up", "w_down")}}
    return out


def layer_forward(x, layer: Dict, positions, length, tc: Dict,
                  low: str = ""):
    """One layer, x [T, d] float32."""
    eps = tc["norm_eps"]
    fp8 = low == "fp8"
    group = (lambda g: _lower_group(g, low)) if low else (lambda g: g)
    if "conv" in layer:
        x = _short_conv(x, group(layer["conv"]), layer["norm1"], eps, fp8)
    else:
        x = _attention(x, group(layer["attn"]), layer["norm1"], positions,
                       length, tc, fp8)
    if "moe" in layer:
        return _experts(x, layer["norm2"], layer["moe"], _sizes(tc), low)
    ffn = group(layer["ffn"])
    z = _normed(x, layer["norm2"], eps, fp8)
    return x + _swiglu(z, ffn["w_gate"], ffn["w_up"], ffn["w_down"], fp8)


def reference_logits(params: Dict, tc: Dict, tokens: np.ndarray,
                     rows: np.ndarray, low: str = "") -> np.ndarray:
    """float32 logits [len(rows), vocab] of the full forward pass over
    ``tokens`` at the positions ``rows``.  ``low`` ("fp8") runs the
    control: the same pass with every matrix and every matrix product's
    activation operand through that precision."""
    n = int(tokens.shape[0])
    padded = -(-n // PAD_TO) * PAD_TO
    toks = np.zeros((padded,), np.int32)
    toks[:n] = tokens  # pad rows come after every real row: causally dead
    positions = jnp.arange(padded, dtype=jnp.int32)
    x = _embed(params["embed"], jnp.asarray(toks))
    for layer in params["layers"]:
        x = layer_forward(x, layer, positions, n, tc, low)
    # the rows too are padded to one shape; the extra rows are dropped
    width = -(-len(rows) // 1024) * 1024
    padded_rows = np.zeros((width,), np.int32)
    padded_rows[:len(rows)] = rows
    lm_head = _LOW[low](params["lm_head"]) if low else params["lm_head"]
    logits = _head(x, jnp.asarray(padded_rows), params["final_norm"]["scale"],
                   lm_head, tc["norm_eps"], low == "fp8")
    return np.asarray(logits[:len(rows)])


def served_gaps(params: Dict, tc: Dict, prompt: np.ndarray,
                served: Sequence[int]) -> np.ndarray:
    """For one request: how far each served token's reference logit lies
    below the reference's best at that position (0 where they agree)."""
    served, tokens, rows = _rows(prompt, served)
    logits = reference_logits(params, tc, tokens, rows)
    return logits.max(-1) - logits[np.arange(len(served)), served]


def control_gaps(params: Dict, tc: Dict, prompt: np.ndarray,
                 served: Sequence[int], kind: str = CONTROL) -> np.ndarray:
    """At each position of the same prompt and tokens, the gap of the token
    the lower precision puts first."""
    served, tokens, rows = _rows(prompt, served)
    logits = reference_logits(params, tc, tokens, rows)
    picked = reference_logits(params, tc, tokens, rows, low=kind).argmax(-1)
    return logits.max(-1) - logits[np.arange(len(served)), picked]
