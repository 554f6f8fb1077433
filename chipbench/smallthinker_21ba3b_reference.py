"""The plain reference of ``configs/smallthinker-21ba3b-instruct.json``: the
first pipeline stage of SmallThinker-21BA3B-Instruct, written from the
equations in straightforward ``jax.numpy``, float32, matmul precision
``highest`` — every row against every earlier row under the layer's own
mask, no cache, no page, nothing held or released, no batching, no grouping
of rows by expert.  Nothing is imported from the program.

d = ``d_model``, H query heads and K KV heads of ``head_width`` hd, all linear
maps without bias, rms(x, w) = x . rsqrt(mean x^2 + eps) . w:

    layer l, input x [T, d]:
      l_r = float32(x) W_r                     ([64]: the router reads the layer's INPUT,
                                                before norm1 and before the attention)
      T   = the top_k of l_r;  g = softmax(l_r[T])        (over the chosen six alone)
      h   = x + Attn_l(rms(x, w_1)) W_o
      y   = rms(h, w_2)
      out = h + sum_{e in T} g_e . (relu(y Wg_e) * (y Wu_e)) Wd_e     (width expert_d_ff)
    Attn_l, ``layer_operators[l]``:
      q = y Wq -> [H, hd],  k, v = y Wk, y Wv -> [K, hd];  no norm over a head
      "global": q and k as they are (NOTHING rotated);  row i sees row j iff j <= i
      "window": q, k turned by rotate-half rope at rope_theta, the absolute position;
                row i sees row j iff 0 <= i - j < attention_window
      softmax(q . k / sqrt(hd)) in float32, a KV head serving H / K query heads
    model:  embed -> layers -> rms(x, w_final) -> untied head

Attention runs in query blocks of ``QUERY_BLOCK`` rows against every key, so
that a 16,384-row request fits (4 x 7 x 256 x 16,384 float32 scores: 470 MB).
Every expert is run on every row and the rows that did not choose it are
weighted 0 (one ``lax.scan`` over the experts).  It is handed the benchmark's
own seeded bf16 weights, which stay on the device (7.9 GB at the cell's size),
and upcasts them a piece at a time: one attention, one expert — never a
layer; the control lowers them the same way.  Teacher-forced over prompt +
served tokens, as the other causal references are; the embedding, the head
and the row bookkeeping are ``longcat_flash_reference``'s.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.longcat_flash_reference import (  # noqa: F401
    _embed, _head, _rms_norm, _rows)
from chipbench.reference import CONTROL, _LOW, _f32, _fp8, summarize  # noqa: F401

PAD_TO = 2048  # sequences are padded to a multiple: few shapes compile
QUERY_BLOCK = 256  # attention runs in query blocks of this many rows
SIZES = ("n_heads", "n_kv_heads", "head_width", "rope_theta", "norm_eps",
         "router_top_k", "attention_window")


def _sizes(tc: Dict):
    return tuple((k, tc[k]) for k in SIZES)


def _rope(x, positions, theta):
    """x [T, heads, hd]; the pairs (x[..., :hd/2], x[..., hd/2:])."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


@partial(jax.jit, static_argnums=(4, 5, 6))
def _attention(x, attn: Dict, norm, positions, operator: str, sizes,
               fp8_inputs=False):
    """x + Attn(rms(x)) W_o of a whole sequence, x [T, d] from row 0, under
    the mask and the rotation of the layer's kind."""
    s = dict(sizes)
    act = _fp8 if fp8_inputs else (lambda a: a)
    h, h_kv, hd = s["n_heads"], s["n_kv_heads"], s["head_width"]
    n = x.shape[0]
    with jax.default_matmul_precision("highest"):
        y = act(_rms_norm(x, norm["scale"], s["norm_eps"]))
        q = (y @ _f32(attn["wq"])).reshape(n, h, hd)
        k = (y @ _f32(attn["wk"])).reshape(n, h_kv, hd)
        v = (y @ _f32(attn["wv"])).reshape(n, h_kv, hd)
        if operator == "window":
            q = _rope(q, positions, s["rope_theta"])
            k = _rope(k, positions, s["rope_theta"])
        out = []
        for start in range(0, n, QUERY_BLOCK):
            qb = q[start:start + QUERY_BLOCK]
            rows = qb.shape[0]
            back = positions[start:start + rows, None] - positions[None, :]
            seen = back >= 0  # [rows, T]: pad rows come last, causally dead
            if operator == "window":
                seen = seen & (back < s["attention_window"])
            scores = jnp.einsum(
                "qkgd,skd->kgqs", qb.reshape(rows, h_kv, h // h_kv, hd),
                k) * hd ** -0.5
            scores = jnp.where(seen[None, None], scores, -jnp.inf)
            ob = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, -1), v)
            out.append(ob.reshape(rows, h, hd))
        o = act(jnp.concatenate(out, 0))
        return x + jnp.einsum("thk,hkd->td", o, _f32(attn["wo"]))


def router_weights(x, router, top_k: int):
    """[T, experts]: g_e where the row chose e, else 0; from the rows the
    router READS (the layer's input)."""
    with jax.default_matmul_precision("highest"):
        logits = x @ _f32(router)
    chosen_logits, chosen = jax.lax.top_k(logits, top_k)
    gate = jax.nn.softmax(chosen_logits, -1)  # over the chosen alone
    return jnp.einsum("tk,tke->te", gate,
                      jax.nn.one_hot(chosen, logits.shape[-1]))


@partial(jax.jit, static_argnums=(4, 5))
def _experts(h, weights, norm, moe: Dict, eps, low: str = ""):
    """h + sum_e weights[:, e] . FFN_e(rms(h)), h [T, d]: every expert over
    every row.  ``low`` lowers each expert's matrices, a tensor each, and
    the activation operands."""
    lower = _LOW[low] if low else (lambda w: w)
    act = _fp8 if low == "fp8" else (lambda a: a)
    y = act(_rms_norm(h, norm["scale"], eps))

    def one(out, expert):
        w_gate, w_up, w_down, weight = expert
        with jax.default_matmul_precision("highest"):
            hidden = jax.nn.relu(y @ _f32(lower(w_gate))) \
                * (y @ _f32(lower(w_up)))
            result = act(hidden) @ _f32(lower(w_down))
        return out + weight[:, None] * result, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (moe["w_gate"], moe["w_up"], moe["w_down"], weights.T))
    return h + out


@partial(jax.jit, static_argnums=(1,))
def _lower_group(group: Dict, kind: str) -> Dict:
    return {k: _LOW[kind](v) for k, v in group.items()}


@partial(jax.jit, static_argnums=(1,))
def lower_precision(layer: Dict, kind: str = CONTROL) -> Dict:
    """One whole layer in the lower precision, for a program that is to
    serve it (the twin's control): every matrix a tensor, each expert's
    too; norm scales stay."""
    low = _LOW[kind]
    moe = layer["moe"]
    return {**layer, "attn": _lower_group(layer["attn"], kind),
            "moe": {**moe, "router": low(moe["router"]),
                    **{k: jax.vmap(low)(moe[k])
                       for k in ("w_gate", "w_up", "w_down")}}}


def layer_forward(x, layer: Dict, operator: str, positions, tc: Dict,
                  low: str = ""):
    """One layer, x [T, d] float32."""
    fp8 = low == "fp8"
    moe = layer["moe"]
    router = _LOW[low](moe["router"]) if low else moe["router"]
    # the router reads x, the layer's input: before norm1, before attention
    weights = router_weights(_fp8(x) if fp8 else x, router,
                             tc["router_top_k"])
    attn = _lower_group(layer["attn"], low) if low else layer["attn"]
    h = _attention(x, attn, layer["norm1"], positions, operator, _sizes(tc),
                   fp8)
    return _experts(h, weights, layer["norm2"], moe, tc["norm_eps"], low)


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
    for layer, operator in zip(params["layers"], tc["layer_operators"]):
        x = layer_forward(x, layer, operator, positions, tc, low)
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
