"""The plain reference of ``configs/joyai-llm-flash.json``: the first
pipeline stage of JoyAI-LLM-Flash, written from the equations in
straightforward ``jax.numpy``, float32, matmul precision ``highest`` — no
cache, no paging, no batching, no grouping of rows by expert, the attention
in its expanded form (a key and a value a head from every latent row).
Nothing is imported from the program.

d = ``d_model``, H = ``n_heads``, all linear maps without bias,
RMSNorm(x) = x . rsqrt(mean x^2 + eps) . g:

    MLA(x), positions p:
      c_q  = RMSNorm(x Wdq)                q = c_q Wuq                 -> [H, nope | rope]   (no sqrt(d / rank) factor)
      a    = x Wdkv                        c_kv = RMSNorm(a[:kv_lora_rank])
      k_rope = rope(a[kv_lora_rank:], p)   (one for all heads);  q_rope = rope(q_rope, p)
      rope turns the pairs (2i, 2i+1) by p . theta^(-2i / rope)
      k_nope_h = c_kv Wuk_h                v_h = c_kv Wuv_h          (Wuk | Wuv = wukv)
      score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s)) / sqrt(nope + rope),  causal
      MLA(x) = concat_h(softmax_s(score_h) v_h) Wo
    FFN_f(y) = (silu(y Wg) * (y Wu)) Wd            (f = d_ff dense, expert_d_ff shared, expert_d_ff an expert)
    MoE(y):  s = sigmoid(float32(y) Wr);   T = the top_k of s + b   (one group)
             w_e = routed_scaling_factor . s_e / (sum_{e' in T} s_e' + 1e-20)  for e in T
             (the bias b chooses, it never weighs)
             MoE(y) = sum_{e in T held here} w_e . FFN_e(y)  +  FFN_shared(y)
    layer l: h = x + MLA(RMSNorm(x));  y = RMSNorm(h);  out = h + (FFN_d_ff(y) if l < first_dense_layers else MoE(y))
    model:   embed -> layers -> RMSNorm -> untied head

Every expert held here is run on every row and the rows that did not choose
it are weighted 0 (one ``lax.scan`` over the experts).  It is handed the
benchmark's own seeded bf16 weights, which stay on the device (11.1 GB at the
cell's size), and upcasts them a piece at a time: one attention, one FFN,
one expert — never a layer; the control lowers them the same way, each
expert's matrix a tensor of its own.  The pieces that are the same
mathematics in ``longcat_flash_reference.py`` (the norm, the rope's pairs,
causal attention in query blocks, SwiGLU, embedding, head) are that file's.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.longcat_flash_reference import (  # noqa: F401
    PAD_TO, _attention, _embed, _head, _normed, _rms_norm, _rope, _rows,
    _swiglu)
from chipbench.reference import CONTROL, _LOW, _f32, _fp8, summarize  # noqa: F401

SIZES = ("d_model", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "rope_theta", "norm_eps", "n_routed_experts",
         "router_top_k", "routed_scaling_factor", "first_expert_held")


def _sizes(tc: Dict):
    return tuple((k, tc.get(k, 0)) for k in SIZES)


@partial(jax.jit, static_argnums=(4, 5))
def _mla(x, attn, norm, positions, sizes, fp8_inputs=False):
    """x + MLA(RMSNorm(x)), x [T, d]."""
    s = dict(sizes)
    act = _fp8 if fp8_inputs else (lambda a: a)
    kr, nope = s["kv_lora_rank"], s["qk_nope_head_dim"]
    eps, theta = s["norm_eps"], s["rope_theta"]
    with jax.default_matmul_precision("highest"):
        y = act(_rms_norm(x, norm["scale"], eps))
        c_q = _rms_norm(y @ _f32(attn["wdq"]), attn["q_norm"]["scale"], eps)
        q = jnp.einsum("tr,rhk->thk", act(c_q), _f32(attn["wuq"]))
        a = y @ _f32(attn["wdkv"])
        c_kv = _rms_norm(a[:, :kr], attn["kv_norm"]["scale"], eps)
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


def router_weights(y, router, bias, sizes):
    """[T, outputs]: w_e where the row chose e, else 0."""
    s = dict(sizes)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(y @ _f32(router))
    _, chosen = jax.lax.top_k(scores + _f32(bias), s["router_top_k"])
    picked = jax.nn.one_hot(chosen, scores.shape[-1]).sum(1) * scores
    return s["routed_scaling_factor"] * picked \
        / (picked.sum(-1, keepdims=True) + 1e-20)


@partial(jax.jit, static_argnums=(2, 3))
def routed_experts(y, moe: Dict, sizes, low: str = ""):
    """sum_{e in T held here} w_e . FFN_e(y), y [T, d] float32 (through
    fp8 already, in the control): the experts ``moe`` holds, from
    ``first_expert_held`` on, each over every row.  ``low`` lowers the
    router and each expert's matrices, a tensor each."""
    lower = _LOW[low] if low else (lambda w: w)
    act = _fp8 if low == "fp8" else (lambda a: a)
    weights = router_weights(y, lower(moe["router"]), moe["bias"], sizes)
    first = dict(sizes)["first_expert_held"]
    held = moe["w_gate"].shape[0]

    def one(out, expert):
        w_gate, w_up, w_down, weight = expert
        with jax.default_matmul_precision("highest"):
            hidden = jax.nn.silu(y @ _f32(lower(w_gate))) \
                * (y @ _f32(lower(w_up)))
            result = act(hidden) @ _f32(lower(w_down))
        return out + weight[:, None] * result, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (moe["w_gate"], moe["w_up"], moe["w_down"],
         weights[:, first:first + held].T))
    return out


@partial(jax.jit, static_argnums=(1,))
def _lower_group(group: Dict, kind: str) -> Dict:
    """One attention's or one FFN's matrices in the lower precision (norm
    scales stay)."""
    return {k: (v if "norm" in k else _LOW[kind](v))
            for k, v in group.items()}


@partial(jax.jit, static_argnums=(1,))
def lower_precision(layer: Dict, kind: str = CONTROL) -> Dict:
    """One whole layer in the lower precision, for a program that is to
    serve it (the twin's control): every matrix a tensor, each expert's
    too; norm scales and the choice bias stay."""
    low = _LOW[kind]
    out = {**layer, "attn": _lower_group(layer["attn"], kind)}
    for name in ("ffn", "shared"):
        if name in layer:
            out[name] = _lower_group(layer[name], kind)
    if "moe" in layer:
        moe = layer["moe"]
        out["moe"] = {**moe, "router": low(moe["router"]),
                      **{k: jax.vmap(low)(moe[k])
                         for k in ("w_gate", "w_up", "w_down")}}
    return out


def layer_forward(x, layer: Dict, positions, sizes, low: str = ""):
    """One layer, x [T, d] float32."""
    eps = dict(sizes)["norm_eps"]
    fp8 = low == "fp8"
    group = (lambda g: _lower_group(g, low)) if low else (lambda g: g)
    ffn = lambda g: _swiglu(y, g["w_gate"], g["w_up"], g["w_down"], fp8)
    x = _mla(x, group(layer["attn"]), layer["norm_attn"], positions, sizes,
             fp8)
    y = _normed(x, layer["norm_ffn"], eps, fp8)
    if "moe" not in layer:
        return x + ffn(group(layer["ffn"]))
    out = x + routed_experts(y, layer["moe"], sizes, low)
    if "shared" in layer:
        out = out + ffn(group(layer["shared"]))
    return out


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
        x = layer_forward(x, layer, positions, sizes, low)
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
