"""The plain reference of the second block (``configs/tiny_moe.json``): the
dense block of ``chipbench/reference.py`` (its norm, rotary positions,
attention, embedding and head are used as they stand) with, in every
``moe_every``-th layer, a routed-expert feed-forward written from the
equations, float32 at matmul precision ``highest``:

    p = softmax(served_dtype(n2(x)) . router)   over the experts
    chosen, g = the top_k experts by p, their p renormalised to sum to 1
    x += sum over chosen e of g_e . W_out[e] . gelu_tanh(W_in[e] . n2(x))

Every expert is run on every row and the unchosen ones weighted 0: nothing
is dispatched, so nothing can be dropped (the program pins its capacity to
the chunk for the same end).  Nothing is imported from the program.

The router's product alone is taken in the served dtype, as the block states
it (bf16 operands, a bf16 result): which experts come first is a discrete
choice made on those logits, and a float32 router puts another expert second
wherever two are all but tied — about one position in 60 at these sizes, each
read as a gap of 0.2-0.3 where every other token reads under 0.011
(``configs/tiny_moe.json``, ``correct.readings``).  Gates, experts and
everything else stay float32.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as dense
from chipbench.reference import _f32, summarize  # noqa: F401

CONTROL = dense.CONTROL


def _routed(y, moe, top_k, act):
    """y [T, d] (already through ``act``) -> [T, d]."""
    router = moe["router"]
    probs = jax.nn.softmax(_f32(y.astype(router.dtype) @ router), -1)
    gate, chosen = jax.lax.top_k(probs, top_k)
    if top_k > 1:
        gate = gate / gate.sum(-1, keepdims=True)
    weight = (jax.nn.one_hot(chosen, probs.shape[-1]) * gate[..., None]).sum(1)
    hidden = jax.nn.gelu(jnp.einsum("td,edf->etf", y, _f32(moe["w_in"])),
                         approximate=True)
    out = jnp.einsum("etf,efd->etd", act(hidden), _f32(moe["w_out"]))
    return jnp.einsum("te,etd->td", weight, out)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _layer(x, layer, positions, use_rope, top_k, fp8_inputs=False):
    act = dense._fp8 if fp8_inputs else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        attn = layer["attn"]
        y = act(dense._rms_norm(x, layer["norm1"]["scale"]))
        q = jnp.einsum("td,dhk->thk", y, _f32(attn["wq"]))
        k = jnp.einsum("td,dhk->thk", y, _f32(attn["wk"]))
        v = jnp.einsum("td,dhk->thk", y, _f32(attn["wv"]))
        if use_rope:
            q, k = dense._rope(q, positions), dense._rope(k, positions)
        o = act(dense._attention(q, k, v))
        x = x + jnp.einsum("thk,hkd->td", o, _f32(attn["wo"]))
        y = act(dense._rms_norm(x, layer["norm2"]["scale"]))
        if "moe" in layer:
            return x + _routed(y, layer["moe"], top_k, act)
        hidden = jax.nn.gelu(y @ _f32(layer["mlp"]["w_in"]), approximate=True)
        return x + act(hidden) @ _f32(layer["mlp"]["w_out"])


@partial(jax.jit, static_argnums=(1,))
def lower_precision(layer: Dict, kind: str = CONTROL) -> Dict:
    """One layer with each of its matrices, the router among them, in the
    lower precision (norm scales stay)."""
    low = dense._LOW[kind]
    return {name: ({k: low(v) for k, v in group.items()}
                   if name in ("attn", "mlp", "moe") else group)
            for name, group in layer.items()}


def reference_logits(params: Dict, tc: Dict, tokens: np.ndarray,
                     rows: np.ndarray, low: str = "") -> np.ndarray:
    """float32 logits [len(rows), vocab] of the full forward pass over
    ``tokens`` at the positions ``rows``; ``low`` runs the control."""
    n = int(tokens.shape[0])
    padded = -(-n // dense.PAD_TO) * dense.PAD_TO
    toks = np.zeros((padded,), np.int32)
    toks[:n] = tokens  # pad rows come after every real row: causally dead
    positions = jnp.arange(padded, dtype=jnp.int32)
    head = {k: v for k, v in params.items() if k in ("embed", "pos_embed")}
    x = dense._embed(head, jnp.asarray(toks), positions)
    for layer in params["layers"]:
        if low:
            layer = lower_precision(layer, low)
        x = _layer(x, layer, positions, tc["positional"] == "rope",
                   tc["moe_top_k"], low == "fp8")
    width = -(-len(rows) // dense.PAD_TO) * dense.PAD_TO
    padded_rows = np.zeros((width,), np.int32)
    padded_rows[:len(rows)] = rows
    lm_head = dense._LOW[low](params["lm_head"]) if low else params["lm_head"]
    logits = dense._head(x, jnp.asarray(padded_rows),
                         params["final_norm"]["scale"], lm_head, low == "fp8")
    return np.asarray(logits[:len(rows)])


def _rows(prompt, served):
    served = np.asarray(served, np.int32)
    tokens = np.concatenate([np.asarray(prompt, np.int32), served])
    return served, tokens, np.arange(len(prompt) - 1, len(tokens) - 1)


def served_gaps(params: Dict, tc: Dict, prompt: np.ndarray,
                served: Sequence[int]) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at that position (0 where they agree)."""
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
