"""The plain reference of ``configs/brumby-14b-base.json``: the first
pipeline stage of Brumby-14B-Base, written from the equations in
straightforward ``jax.numpy``, float32, matmul precision ``highest`` — the
QUADRATIC form: every row against every earlier row.  No state, no ``phi``,
no cache, no paging, no batching.  Nothing is imported from the program, and
it is independent of it by construction: the program serves everything
before a lane's last key block from a recurrent state; this never forms one.

d = ``d_model``, H = ``n_heads`` query heads, K = ``n_kv_heads`` (the 5
query heads of a KV head share its keys, values and gate), hd =
``head_width``; all linear maps without bias but the gate's;
RMSNorm(x) = x . rsqrt(mean x^2 + eps) . g:

    y      = RMSNorm(x)
    q, k   = rope(RMSNorm_head(y Wq)), rope(RMSNorm_head(y Wk))   v = y Wv
             (the norm over the hd of a head; rope turns the halves
             (u[:hd/2], u[hd/2:]) by p . theta^(-i / (hd/2)))
    a_t    = logsigmoid(y_t . w_g,h + b_g,h)        A_t = a_1 + ... + a_t
    w_ij   = exp(A_i - A_j) . (q_i . k_j / sqrt(hd))^2          for j <= i
    o_i    = sum_j w_ij v_j / (sum_j w_ij + 1e-6)
    layer:   h = x + concat_heads(o) Wo;   out = h + (silu(y' Wg) * (y' Wu)) Wd,  y' = RMSNorm(h)
    model:   embed -> layers -> RMSNorm -> untied head

Query rows go in blocks of ``QUERY_BLOCK`` (a block's weights against 8,192
keys are 0.67 GB in float32), so a request of 8,192 rows fits beside the
weights.  It is handed the benchmark's own seeded bf16 weights, which stay on
the device (6.4 GB at the cell's size), and upcasts them a matrix at a time;
the control lowers them the same way (the gate's map too; its bias and the
norms stay).  The pieces that are the same mathematics elsewhere (the norm
and the rope of ``sdar_30b_a3b_chat_reference.py``, whose q/k projections
these are; SwiGLU, embedding, head of ``longcat_flash_reference.py``) are
those files'.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.longcat_flash_reference import (  # noqa: F401
    _embed, _head, _rows, _swiglu)
from chipbench.reference import CONTROL, _LOW, _f32, _fp8, summarize  # noqa: F401
from chipbench.sdar_30b_a3b_chat_reference import _qkv, _rms_norm

SIZES = ("d_model", "n_heads", "n_kv_heads", "head_width", "rope_theta",
         "norm_eps")
PAD_TO = 4096  # sequences are padded to a multiple: one shape compiles, not one a length
QUERY_BLOCK = 512  # the weights run in query blocks of this many rows
EPS = 1e-6  # the quotient's


def _sizes(tc: Dict):
    return tuple((k, tc.get(k, 0)) for k in SIZES)


def _retention(q, k, v, a):
    """q [T, H, hd]; k, v [T, K, hd]; a [T, K] log gates -> o [T, H, hd]:
    the weights above, every row against every earlier row."""
    t, h, hd = q.shape
    h_kv = k.shape[1]
    running = jnp.cumsum(a, axis=0)  # A_t, [T, K]
    key_pos = jnp.arange(t)
    out = []
    for start in range(0, t, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK].reshape(-1, h_kv, h // h_kv, hd)
        rows = qb.shape[0]
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * hd ** -0.5
        seen = key_pos[None, :] <= (start + jnp.arange(rows))[:, None]
        log_decay = (running[start:start + rows].T[:, :, None]
                     - running.T[:, None, :])  # A_i - A_j, [K, Q, S]
        decay = jnp.exp(jnp.where(seen[None], log_decay, -jnp.inf))
        weights = decay[:, None] * scores * scores  # [K, G, Q, S]
        num = jnp.einsum("kgqs,skd->qkgd", weights, v)
        den = jnp.sum(weights, -1).transpose(2, 0, 1)  # [Q, K, G]
        out.append((num / (den[..., None] + EPS)).reshape(rows, h, hd))
    return jnp.concatenate(out, 0)


@partial(jax.jit, static_argnums=(3, 4))
def layer_forward(x, layer: Dict, positions, sizes, fp8_inputs: bool = False):
    """One layer over x [T, d].  ``fp8_inputs`` is the control's: every
    matrix product's activation operand goes through fp8 (the weights
    already have)."""
    s = dict(sizes)
    act = _fp8 if fp8_inputs else (lambda u: u)
    attn, eps = layer["attn"], s["norm_eps"]
    d, hd = x.shape[-1], s["head_width"]
    # the three input projections are handed over as matrices [d, heads x hd]
    by_head = {**attn, **{name: attn[name].reshape(d, -1, hd)
                          for name in ("wq", "wk", "wv")}}
    with jax.default_matmul_precision("highest"):
        q, k, v = _qkv(x, by_head, layer["norm1"], positions, s, act)
        y = act(_rms_norm(x, layer["norm1"]["scale"], eps))
        a = jax.nn.log_sigmoid(y @ _f32(attn["gate"]["w"])
                               + _f32(attn["gate"]["b"]))
        o = act(_retention(q, k, v, a))
        h = x + jnp.einsum("thk,hkd->td", o, _f32(attn["wo"]))
        y = act(_rms_norm(h, layer["norm2"]["scale"], eps))
    ffn = layer["ffn"]
    return h + _swiglu(y, ffn["w_gate"], ffn["w_up"], ffn["w_down"],
                       fp8_inputs)


@partial(jax.jit, static_argnums=(1,))
def lower_precision(layer: Dict, kind: str = CONTROL) -> Dict:
    """One whole layer in the lower precision: every matrix a tensor, the
    gate's map among them; its bias and the norm scales stay."""
    low, attn = _LOW[kind], layer["attn"]
    return {**layer,
            "attn": {**attn,
                     **{k: low(attn[k]) for k in ("wq", "wk", "wv", "wo")},
                     "gate": {**attn["gate"], "w": low(attn["gate"]["w"])}},
            "ffn": {k: low(v) for k, v in layer["ffn"].items()}}


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
    sizes = _sizes(tc)
    x = _embed(params["embed"], jnp.asarray(toks))
    for layer in params["layers"]:
        if low:
            layer = lower_precision(layer, low)
        x = layer_forward(x, layer, positions, sizes, low == "fp8")
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
    """The control needs no decoding: at each position of the same prompt
    and tokens, the gap of the token the lower precision puts first."""
    served, tokens, rows = _rows(prompt, served)
    logits = reference_logits(params, tc, tokens, rows)
    picked = reference_logits(params, tc, tokens, rows, low=kind).argmax(-1)
    return logits.max(-1) - logits[np.arange(len(served)), picked]
