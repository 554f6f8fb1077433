"""The plain reference: the repository's one decoder block in straightforward
``jax.numpy``, float32, matmul precision ``highest`` — no cache, no paging,
no batching, nothing imported from the program.

The block (what both configurations run through ``ServingEngine``):
token embedding (+ a learned position table, or rotary positions in the
split-half convention at base 10000), then per layer
``x += Wo . attn(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))`` with causal
grouped-query attention scaled by ``head_dim ** -0.5`` and
``x += W_out . gelu_tanh(W_in n2(x))``, RMSNorm (eps 1e-6, scale only)
before both, a final RMSNorm and an untied output head.  No biases.

It is handed the benchmark's own seeded bf16 weights and upcasts them a
layer at a time, so nothing the size of the model is ever held in float32.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ROPE_BASE = 10000.0
NORM_EPS = 1e-6
PAD_TO = 4096  # sequences are padded to a multiple: one shape compiles, not one a length
QUERY_BLOCK = 512  # attention runs in query blocks of this many rows


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * _f32(scale)


def _rope(x, positions):
    """x [T, heads, hd]; pairs (x[..., :hd/2], x[..., hd/2:])."""
    half = x.shape[-1] // 2
    inv = 1.0 / ROPE_BASE ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attention(q, k, v):
    """Causal grouped-query attention.  q [T, h, hd]; k, v [T, h_kv, hd]."""
    t, h, hd = q.shape
    h_kv = k.shape[1]
    group = h // h_kv
    key_pos = jnp.arange(t)
    out = []
    for start in range(0, t, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK].reshape(-1, h_kv, group, hd)
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * hd ** -0.5
        q_pos = start + jnp.arange(qb.shape[0])
        mask = key_pos[None, :] <= q_pos[:, None]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, -1)
        ob = jnp.einsum("kgqs,skd->qkgd", probs, v)
        out.append(ob.reshape(-1, h, hd))
    return jnp.concatenate(out, 0)


def _fp8(x):
    """Through float8 e4m3 and back, scaled so that the largest magnitude
    lands on the format's largest value (per tensor, as fp8 matmuls are
    run)."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


@partial(jax.jit, static_argnums=(3, 4))
def _layer(x, layer, positions, use_rope, fp8_inputs=False):
    """``fp8_inputs`` is the control's: every matrix product's activation
    operand goes through fp8 (its weights already have)."""
    act = _fp8 if fp8_inputs else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        attn = layer["attn"]
        y = act(_rms_norm(x, layer["norm1"]["scale"]))
        q = jnp.einsum("td,dhk->thk", y, _f32(attn["wq"]))
        k = jnp.einsum("td,dhk->thk", y, _f32(attn["wk"]))
        v = jnp.einsum("td,dhk->thk", y, _f32(attn["wv"]))
        if use_rope:
            q, k = _rope(q, positions), _rope(k, positions)
        o = act(_attention(q, k, v))
        x = x + jnp.einsum("thk,hkd->td", o, _f32(attn["wo"]))
        y = act(_rms_norm(x, layer["norm2"]["scale"]))
        hidden = jax.nn.gelu(y @ _f32(layer["mlp"]["w_in"]), approximate=True)
        return x + act(hidden) @ _f32(layer["mlp"]["w_out"])


@jax.jit
def _embed(params, tokens, positions):
    x = _f32(params["embed"][tokens])
    if "pos_embed" in params:
        x = x + _f32(params["pos_embed"][positions])
    return x


@partial(jax.jit, static_argnums=(4,))
def _head(x, rows, scale, lm_head, fp8_inputs=False):
    act = _fp8 if fp8_inputs else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        return act(_rms_norm(x[rows], scale)) @ _f32(lm_head)


def reference_logits(params: Dict, tc: Dict, tokens: np.ndarray,
                     rows: np.ndarray, low: str = "") -> np.ndarray:
    """float32 logits [len(rows), vocab] of the full forward pass over
    ``tokens`` at the positions ``rows``.  ``low`` ("fp8" or "int8") runs
    the control: the same pass in that precision (see ``CONTROL``)."""
    n = int(tokens.shape[0])
    window = tc.get("attention_window")
    if window is not None and n > window:
        raise ValueError(f"{n} positions exceed the window {window}: this "
                         f"reference has no band, the cells never need one")
    padded = -(-n // PAD_TO) * PAD_TO
    toks = np.zeros((padded,), np.int32)
    toks[:n] = tokens  # pad rows come after every real row: causally dead
    positions = jnp.arange(padded, dtype=jnp.int32)
    head = {k: v for k, v in params.items() if k in ("embed", "pos_embed")}
    x = _embed(head, jnp.asarray(toks), positions)
    for layer in params["layers"]:
        if low:
            layer = lower_precision(layer, low)
        x = _layer(x, layer, positions, tc["positional"] == "rope",
                   low == "fp8")
    # the rows too are padded to one shape; the extra rows are dropped
    width = -(-len(rows) // PAD_TO) * PAD_TO
    padded_rows = np.zeros((width,), np.int32)
    padded_rows[:len(rows)] = rows
    lm_head = _LOW[low](params["lm_head"]) if low else params["lm_head"]
    logits = _head(x, jnp.asarray(padded_rows),
                   params["final_norm"]["scale"], lm_head, low == "fp8")
    return np.asarray(logits[:len(rows)])


def served_gaps(params: Dict, tc: Dict, prompt: np.ndarray,
                served: Sequence[int]) -> np.ndarray:
    """For one request: how far each served token's reference logit lies
    below the reference's best at that position (0 where they agree)."""
    served = np.asarray(served, np.int32)
    tokens = np.concatenate([np.asarray(prompt, np.int32), served])
    rows = np.arange(len(prompt) - 1, len(tokens) - 1)
    logits = reference_logits(params, tc, tokens, rows)
    return logits.max(-1) - logits[np.arange(len(served)), served]


# ---------------------------------------------------------------------------
# the control: the same reference in the precision below bfloat16
# ---------------------------------------------------------------------------

# The configurations state bfloat16, so the step below is int8 or fp8.
# "fp8" (float8 e4m3 for the weights AND the activation operand of every
# matrix product, per-tensor scaled) is the control that has to fail, and
# does on every seed.  "int8" (weights only, symmetric per output channel)
# is read beside it: greedy tokens cannot tell all of it from the program's
# own bf16 logits, which already sit on a grid as coarse as int8 weights make
# them, and each configuration's ``correct`` block says how much of its range
# the limits do catch (PERF.md section 2).
CONTROL = "fp8"


@jax.jit
def _int8_weight(w):
    f = _f32(w)
    reduce_axes = tuple(range(f.ndim - 1))
    scale = jnp.max(jnp.abs(f), axis=reduce_axes, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (jnp.clip(jnp.round(f / scale), -127, 127) * scale).astype(w.dtype)


@jax.jit
def _fp8_weight(w):
    return _fp8(_f32(w)).astype(w.dtype)


_LOW = {"int8": _int8_weight, "fp8": _fp8_weight}


@partial(jax.jit, static_argnums=(1,))
def lower_precision(layer: Dict, kind: str = CONTROL) -> Dict:
    """One layer with each of its matrices in the lower precision (norm
    scales stay); the control converts a layer at a time, so no second
    copy of the model is ever held."""
    low = _LOW[kind]
    return {**layer,
            "attn": {k: low(v) for k, v in layer["attn"].items()},
            "mlp": {k: low(v) for k, v in layer["mlp"].items()}}


def control_gaps(params: Dict, tc: Dict, prompt: np.ndarray,
                 served: Sequence[int], kind: str = CONTROL) -> np.ndarray:
    """The control needs no decoding: at each position of the same prompt
    and tokens, the gap of the token the lower precision puts first."""
    served = np.asarray(served, np.int32)
    tokens = np.concatenate([np.asarray(prompt, np.int32), served])
    rows = np.arange(len(prompt) - 1, len(tokens) - 1)
    logits = reference_logits(params, tc, tokens, rows)
    picked = reference_logits(params, tc, tokens, rows, low=kind).argmax(-1)
    return logits.max(-1) - logits[np.arange(len(served)), picked]


def summarize(gaps: List[np.ndarray]) -> Dict[str, float]:
    """The numbers compared: the widest gap and the mean gap over all
    served tokens of the sample."""
    flat = np.concatenate(gaps)
    return {"widest_gap": float(flat.max()), "mean_gap": float(flat.mean()),
            "tokens": int(flat.size),
            "off_best": int(np.count_nonzero(flat > 0))}
