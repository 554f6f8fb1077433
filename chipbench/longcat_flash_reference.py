"""The plain reference of ``configs/longcat-flash-chat.json``: one
expert-parallel rank of LongCat-Flash-Chat, written from the equations in
straightforward ``jax.numpy``, float32, matmul precision ``highest`` — no
cache, no paging, no batching, no grouping of rows by expert, the attention
in its expanded form (a key and a value a head from every latent row).
Nothing is imported from the program.

d = ``d_model``, H = ``n_heads``, all linear maps without bias,
RMSNorm(x) = x . rsqrt(mean x^2 + eps) . g.  Layer l, sub-layers j in {0, 1}:

    MLA_j(x), positions p:
      c_q  = RMSNorm(x Wdq)                q = (c_q Wuq) . sqrt(d / q_lora_rank)      -> [H, nope | rope]
      a    = x Wdkv                        c_kv = RMSNorm(a[:kv_lora_rank]) . sqrt(d / kv_lora_rank)
      k_rope = rope(a[kv_lora_rank:], p)   (one for all heads);  q_rope = rope(q_rope, p)
      rope turns the pairs (2i, 2i+1) by p . theta^(-2i / rope)
      k_nope_h = c_kv Wuk_h                v_h = c_kv Wuv_h          (Wuk | Wuv = wukv)
      score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s)) / sqrt(nope + rope),  causal
      MLA_j(x) = concat_h(softmax_s(score_h) v_h) Wo
    FFN_j(y) = (silu(y Wg) * (y Wu)) Wd
    MoE(y):  P = softmax(float32(y) Wr) over routed + zero outputs;  T = the top_k of P
             w_e = routed_scaling_factor . P_e for e in T (not renormalised)
             MoE(y) = sum_{e in T held here} w_e . (silu(y Wg_e) * (y Wu_e)) Wd_e
                    + sum_{e in T, e >= n_routed_experts} w_e . y              (identity experts)
             a choice of a routed expert held on another rank adds nothing here
    layer:   h1 = x + MLA_0(RMSNorm(x));   y0 = RMSNorm(h1);  m = MoE(y0);  h2 = h1 + FFN_0(y0)
             h3 = h2 + MLA_1(RMSNorm(h2));  y1 = RMSNorm(h3);  out = h3 + FFN_1(y1) + m
    model:   embed -> layers -> RMSNorm -> untied head

Every expert held here is run on every row and the rows that did not choose
it are weighted 0.  It is handed the benchmark's own seeded bf16 weights,
which stay on the device (10.35 GB at the cell's size), and upcasts them a
piece at a time: one attention, one FFN, one expert — never a layer.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import CONTROL, _LOW, _f32, _fp8, summarize  # noqa: F401

PAD_TO = 2048  # sequences are padded to a multiple: few shapes compile
QUERY_BLOCK = 256  # attention runs in query blocks of this many rows
SIZES = ("d_model", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "rope_theta", "norm_eps", "n_routed_experts",
         "router_top_k", "routed_scaling_factor", "first_expert_held")


def _sizes(tc: Dict):
    return tuple((k, tc[k]) for k in SIZES)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(scale)


def _rope(x, positions, theta):
    """x [T, ..., rope]: the pairs (2i, 2i+1) turned in place."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv  # [T, half]
    angle = angle.reshape(angle.shape[:1] + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1) \
        .reshape(x.shape)


def _attention(q, k, v):
    """Causal attention.  q, k [T, H, nope + rope]; v [T, H, vd]."""
    t = q.shape[0]
    key_pos = jnp.arange(t)
    out = []
    for start in range(0, t, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        scores = jnp.einsum("qhd,shd->hqs", qb, k) * q.shape[-1] ** -0.5
        q_pos = start + jnp.arange(qb.shape[0])
        scores = jnp.where((key_pos[None, :] <= q_pos[:, None])[None],
                           scores, -jnp.inf)
        out.append(jnp.einsum("hqs,shd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(out, 0)


@partial(jax.jit, static_argnums=(4, 5))
def _mla(x, attn, norm, positions, sizes, fp8_inputs=False):
    """x + MLA(RMSNorm(x)), x [T, d]."""
    s = dict(sizes)
    act = _fp8 if fp8_inputs else (lambda a: a)
    d, kr, nope = s["d_model"], s["kv_lora_rank"], s["qk_nope_head_dim"]
    eps, theta = s["norm_eps"], s["rope_theta"]
    with jax.default_matmul_precision("highest"):
        y = act(_rms_norm(x, norm["scale"], eps))
        c_q = _rms_norm(y @ _f32(attn["wdq"]), attn["q_norm"]["scale"], eps)
        q = jnp.einsum("tr,rhk->thk", act(c_q), _f32(attn["wuq"])) \
            * (d / s["q_lora_rank"]) ** 0.5
        a = y @ _f32(attn["wdkv"])
        c_kv = _rms_norm(a[:, :kr], attn["kv_norm"]["scale"], eps) \
            * (d / kr) ** 0.5
        k_rope = _rope(a[:, kr:], positions, theta)  # [T, rope]
        q = jnp.concatenate([q[..., :nope],
                             _rope(q[..., nope:], positions, theta)], -1)
        kv = jnp.einsum("tr,rhk->thk", act(c_kv), _f32(attn["wukv"]))
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope[:, None, :],
                              kv.shape[:2] + k_rope.shape[-1:])], -1)
        o = act(_attention(q, k, kv[..., nope:]))
        return x + jnp.einsum("thm,hmd->td", o, _f32(attn["wo"]))


@partial(jax.jit, static_argnums=(3,))
def _normed(x, norm, eps, fp8_inputs=False):
    y = _rms_norm(x, norm["scale"], eps)
    return _fp8(y) if fp8_inputs else y


@partial(jax.jit, static_argnums=(4,))
def _swiglu(y, w_gate, w_up, w_down, fp8_inputs=False):
    """(silu(y Wg) * (y Wu)) Wd: a dense FFN, or one expert."""
    act = _fp8 if fp8_inputs else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        hidden = jax.nn.silu(y @ _f32(w_gate)) * (y @ _f32(w_up))
        return act(hidden) @ _f32(w_down)


@partial(jax.jit, static_argnums=(2,))
def _choices(y, router, sizes):
    """[T, outputs]: w_e where the row chose e, else 0."""
    s = dict(sizes)
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(y @ _f32(router), -1)
    gate, chosen = jax.lax.top_k(probs, s["router_top_k"])
    return (jax.nn.one_hot(chosen, probs.shape[-1])
            * (gate * s["routed_scaling_factor"])[..., None]).sum(1)


def expert_layer(y, moe: Dict, sizes, fp8_inputs: bool = False):
    """MoE(y), y [T, d] float32 (through fp8 already, in the control):
    this rank's share — the experts ``moe`` holds, from
    ``first_expert_held`` on — and the identity experts' part."""
    s = dict(sizes)
    weights = _choices(y, moe["router"], sizes)
    out = weights[:, s["n_routed_experts"]:].sum(-1, keepdims=True) * y
    first = s["first_expert_held"]
    for e in range(moe["w_gate"].shape[0]):
        out = out + weights[:, first + e, None] * _swiglu(
            y, moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e],
            fp8_inputs)
    return out


def layer_forward(x, layer: Dict, positions, sizes, fp8_inputs: bool = False):
    """One double layer, x [T, d] float32."""
    eps = dict(sizes)["norm_eps"]
    m = None
    for j in range(2):
        x = _mla(x, layer["attn"][j], layer["norm_attn"][j], positions,
                 sizes, fp8_inputs)
        y = _normed(x, layer["norm_ffn"][j], eps, fp8_inputs)
        if j == 0:
            m = expert_layer(y, layer["moe"], sizes, fp8_inputs)
        ffn = layer["ffn"][j]
        x = x + _swiglu(y, ffn["w_gate"], ffn["w_up"], ffn["w_down"],
                        fp8_inputs)
    return x + m


@jax.jit
def _embed(embed, tokens):
    return _f32(embed[tokens])


@partial(jax.jit, static_argnums=(4, 5))
def _head(x, rows, scale, lm_head, eps, fp8_inputs=False):
    act = _fp8 if fp8_inputs else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        return act(_rms_norm(x[rows], scale, eps)) @ _f32(lm_head)


@partial(jax.jit, static_argnums=(1,))
def lower_precision(layer: Dict, kind: str = CONTROL) -> Dict:
    """One layer with each of its matrices, the router among them, in the
    lower precision (norm scales stay)."""
    low = _LOW[kind]

    def lower(group):
        return {k: (v if "norm" in k else low(v)) for k, v in group.items()}

    return {**layer,
            "attn": [lower(a) for a in layer["attn"]],
            "ffn": [lower(f) for f in layer["ffn"]],
            "moe": lower(layer["moe"])}


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


def _rows(prompt, served):
    served = np.asarray(served, np.int32)
    tokens = np.concatenate([np.asarray(prompt, np.int32), served])
    return served, tokens, np.arange(len(prompt) - 1, len(tokens) - 1)


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
