"""Mixture-of-Experts layer with expert parallelism over an ``ep`` mesh axis.

Experts are sharded across devices; tokens are routed top-k (top-1 Switch
style by default, top-2 GShard style via ``top_k=2``) and exchanged with the
expert owners.  Two dispatch strategies, numerically identical:

- ``"scatter"`` (default): kept token-choices scatter-add into the
  ``[e, capacity, d]`` expert buffers and gather back out — O(k*n*d) memory
  traffic, no dispatch FLOPs.  Slot positions are unique per expert, so the
  scatter is a permutation (deterministic, exact-VJP gather transpose).
- ``"einsum"``: the classic dense one-hot dispatch/combine einsums whose
  contraction XLA lowers to an all-to-all over ICI when the expert axis is
  sharded.  Costs O(n * e * capacity * d) ~ O(cf * k * n^2 * d) MXU FLOPs —
  quadratic in tokens; at flagship sizes the dispatch einsums burn more
  FLOPs than the expert FFNs themselves (the measured 37% vs 57% MFU gap,
  VERDICT r3 #4).

Both keep everything static-shaped; capacity_factor bounds the per-expert
buffer exactly like token-dropping MoE implementations.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class MoEConfig:
    d_model: int = 512
    d_ff: int = 1024
    num_experts: int = 8
    capacity_factor: float = 1.25
    # routing fan-out: 1 = Switch (gate is the raw top prob), >1 = GShard
    # style (gates renormalized over the chosen experts)
    top_k: int = 1
    # "tokens_choose": classic top-k routing (above).  "experts_choose":
    # expert-choice routing (Zhou et al. 2022) — each expert takes its
    # top-capacity tokens, so load is perfectly balanced by construction
    # and nothing is ever dropped; training-time only for causal LMs (an
    # expert's choices depend on the whole batch/sequence, so it cannot
    # be replayed token-by-token at decode)
    routing: str = "tokens_choose"
    # "scatter" (default): permutation scatter/gather dispatch, O(k*n*d)
    # traffic and no dispatch FLOPs.  "einsum": dense one-hot dispatch
    # einsums, O(cf*k*n^2*d) FLOPs (see module docstring).
    dispatch: str = "scatter"


def moe_init(rng: jax.Array, config: MoEConfig) -> Dict:
    k_router, k_in, k_out = jax.random.split(rng, 3)
    d, f, e = config.d_model, config.d_ff, config.num_experts
    scale_in = (1.0 / d) ** 0.5
    scale_out = (1.0 / f) ** 0.5
    return {
        "router": jax.random.normal(k_router, (d, e), jnp.float32) * scale_in,
        "w_in": jax.random.normal(k_in, (e, d, f), jnp.float32) * scale_in,
        "w_out": jax.random.normal(k_out, (e, f, d), jnp.float32) * scale_out,
    }


def moe_apply(
    params: Dict,
    x: jax.Array,
    config: MoEConfig,
    capacity: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """x: [batch, seq, d_model] -> (output, aux_loss).

    Top-k routing with capacity-bounded dense dispatch; aux_loss is the
    standard load-balancing term (mean_prob * mean_first_choice * E).
    With ``top_k=1`` the gate is the raw top probability (Switch); with
    ``top_k>1`` gates are renormalized over the chosen experts (GShard).

    ``capacity`` overrides the derived per-expert buffer size; pass
    ``capacity=n_tokens`` to guarantee no token-choice is ever dropped
    (a token routes to each expert at most once, so n slots always
    suffice — the incremental-decode path relies on this).
    """
    b, s, d = x.shape
    e = config.num_experts
    k = config.top_k
    if not 1 <= k <= e:
        raise ValueError(f"top_k must be in [1, num_experts], got {k}")
    if config.routing not in ("tokens_choose", "experts_choose"):
        raise ValueError(f"unknown routing {config.routing!r}")
    if config.dispatch not in ("scatter", "einsum"):
        raise ValueError(f"unknown dispatch {config.dispatch!r}")
    tokens = x.reshape(b * s, d)
    n = tokens.shape[0]
    if capacity is None:
        # top_k is a tokens_choose fan-out; expert-choice capacity follows
        # the cf*n/e convention regardless of it
        fanout = k if config.routing == "tokens_choose" else 1
        capacity = max(1, math.ceil(config.capacity_factor * fanout * n / e))
    elif capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")

    logits = tokens @ params["router"]  # [n, e]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if config.routing == "experts_choose":
        return _experts_choose(params, x, tokens, probs, config,
                               min(capacity, n))
    topk_gate, topk_index = jax.lax.top_k(probs, k)  # [n, k]
    if k > 1:
        topk_gate = topk_gate / jnp.sum(topk_gate, axis=-1, keepdims=True)

    # Buffer-slot assignment runs choice-rank-major: every token's first
    # choice claims a slot before any token's second choice, so overflow
    # drops the weakest assignments first.  Flatten [n, k] -> [k*n] in that
    # order, then the top-1 cumsum trick applies unchanged; beyond-capacity
    # assignments are dropped (standard token-dropping MoE).
    onehot = jax.nn.one_hot(topk_index, e, dtype=jnp.int32)  # [n, k, e]
    onehot_flat = onehot.transpose(1, 0, 2).reshape(k * n, e)
    position_in_expert = jnp.cumsum(onehot_flat, axis=0) * onehot_flat  # 1-based
    within_capacity = (position_in_expert <= capacity) & (onehot_flat > 0)
    position = (position_in_expert - 1).max(axis=-1)  # [k*n]

    if config.dispatch == "scatter":
        combined = _scatter_dispatch_combine(
            params, tokens, topk_index, topk_gate, within_capacity,
            position, e, capacity, x.dtype,
        )
    else:
        # per-choice dense dispatch [k, n, e, capacity]; choices occupy
        # disjoint slots, so summing over k gives the 0/1 input dispatch
        dispatch_k = (
            within_capacity[:, :, None]
            & (jax.nn.one_hot(position, capacity, dtype=jnp.int32)[:, None, :] > 0)
        ).astype(x.dtype).reshape(k, n, e, capacity)
        dispatch = dispatch_k.sum(axis=0)  # [n, e, capacity]
        # combine weights fold in the (kept-masked) per-choice gates
        combine = jnp.einsum(
            "kn,knec->nec", topk_gate.T.astype(x.dtype), dispatch_k
        )

        combined = _dispatch_experts_combine(params, tokens, dispatch,
                                             combine, x.dtype)

    # load-balancing auxiliary loss over first choices (Switch/GShard style)
    assignment_fraction = jnp.mean(onehot[:, 0, :].astype(jnp.float32), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux_loss = jnp.sum(assignment_fraction * mean_probs) * e

    return combined.reshape(b, s, d), aux_loss


def _expert_ffn(params, expert_inputs, dtype):
    """Every expert's MLP over its [e, cap, d] token buffer — the batched
    matmuls both dispatch strategies feed."""
    hidden = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", expert_inputs, params["w_in"].astype(dtype))
    )
    return jnp.einsum("ecf,efd->ecd", hidden, params["w_out"].astype(dtype))


def _dispatch_experts_combine(params, tokens, dispatch, combine, dtype):
    """Dense-einsum dispatch body: gather token buffers per expert
    ([n, e, cap] dispatch), run every expert's MLP, and weight results
    back per token ([n, e, cap] combine)."""
    expert_inputs = jnp.einsum("nec,nd->ecd", dispatch, tokens)  # [e, cap, d]
    expert_outputs = _expert_ffn(params, expert_inputs, dtype)
    return jnp.einsum("nec,ecd->nd", combine, expert_outputs)


def _scatter_dispatch_combine(params, tokens, topk_index, topk_gate,
                              within_capacity, position, e, capacity, dtype):
    """Permutation dispatch: every kept (token, choice) owns a unique
    (expert, position) buffer slot, so dispatch is a scatter-add that
    never collides (deterministic) and combine is a plain gather — the
    whole exchange is O(k*n*d) memory traffic with zero matmul FLOPs,
    against the dense path's O(n * e * cap * d) einsums (VERDICT r3 #4).
    Dropped choices route to a sentinel row that is sliced off."""
    n, d = tokens.shape
    k = topk_index.shape[1]
    # choice-rank-major flat order, matching position's cumsum order
    flat_expert = topk_index.T.reshape(k * n)
    keep = within_capacity.any(axis=-1)  # [k*n]
    slot = jnp.where(keep, flat_expert * capacity + position, e * capacity)
    token_idx = jnp.tile(jnp.arange(n), k)
    buf = jnp.zeros((e * capacity + 1, d), dtype)
    buf = buf.at[slot].add(tokens[token_idx])
    expert_outputs = _expert_ffn(params, buf[:-1].reshape(e, capacity, d),
                                 dtype)
    flat_out = jnp.concatenate(
        [expert_outputs.reshape(e * capacity, d), jnp.zeros((1, d), dtype)]
    )
    gates = topk_gate.T.reshape(k * n).astype(dtype) * keep.astype(dtype)
    picked = flat_out[slot] * gates[:, None]  # [k*n, d]
    return picked.reshape(k, n, d).sum(axis=0)


def _experts_choose(params, x, tokens, probs, config, capacity):
    """Expert-choice routing: every expert selects its ``capacity``
    highest-affinity tokens — load is balanced by construction, no token
    dropping, no load-balancing aux loss needed (returned aux is 0).  A
    token may be picked by several experts (outputs sum, gated by the
    picking expert's affinity) or by none (output 0, like a dropped
    token in top-k routing — the residual connection carries it)."""
    b, s, d = x.shape
    e = config.num_experts
    n = tokens.shape[0]

    gates, picks = jax.lax.top_k(probs.T, capacity)  # [e, capacity]
    if config.dispatch == "scatter":
        # picks IS the dispatch: buffer slot (j, c) holds token picks[j, c]
        # — dispatch is a gather, combine a scatter-add back per token
        expert_outputs = _expert_ffn(params, tokens[picks], x.dtype)
        weighted = expert_outputs * gates.astype(x.dtype)[..., None]
        combined = (
            jnp.zeros_like(tokens)
            .at[picks.reshape(-1)]
            .add(weighted.reshape(e * capacity, d))
        )
    else:
        # dense dispatch [n, e, capacity]: slot c of expert j holds token
        # picks[j, c]
        dispatch = (
            jax.nn.one_hot(picks, n, dtype=jnp.int32)  # [e, cap, n]
            .transpose(2, 0, 1)
            .astype(x.dtype)
        )
        combine = dispatch * gates.astype(x.dtype)[None, :, :]

        combined = _dispatch_experts_combine(params, tokens, dispatch,
                                             combine, x.dtype)
    return combined.reshape(b, s, d), jnp.float32(0.0)


# ---------------------------------------------------------------------------
# a routed expert layer without capacity: the law of the router, and the
# share of the layer that THIS device computes
# ---------------------------------------------------------------------------

EXPERT_TILE = 128  # rows of one grouped-matmul tile, at most

# what routed_experts_apply counts: router choices by where the chosen
# expert lives (held here, zero-compute, held elsewhere), the held experts
# that got at least one row, and the tiles its loop ran with their rows
# (an expert's last tile is padded, so tile_rows >= held)
ROUTING_COUNTS = ("held", "zero", "absent", "touched", "tiles", "tile_rows")


def expert_tile_rows(n: int) -> int:
    """Rows a tile for an ``n``-row pass: a power of two, 8 to
    ``EXPERT_TILE``.  A tile is one expert's: its three matrices are read
    once a tile, so a large tile costs a pass of few rows (a decode
    step) padding FLOPs, and a small one costs a pass of many rows (a
    prefill chunk) re-reads of the weights."""
    return min(EXPERT_TILE, max(8, 1 << (max(n, 1) - 1).bit_length()))


def router_choices(logits: jax.Array, bias: Optional[jax.Array], *,
                   top_k: int, scale: float, scoring: str = "softmax",
                   renormalise: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """The router's law: a row's float32 ``logits`` [n, outputs] ->
    (``gate`` [n, top_k] float32, ``index`` [n, top_k]).

    A row's scores are the softmax over all its outputs (``scoring``
    "softmax") or each output's sigmoid ("sigmoid").  It chooses the
    ``top_k`` largest of ``scores + bias``: the bias (None: none) moves
    the CHOICE only and never the weight, which is the chosen expert's
    own score — renormalised over the chosen ones (``renormalise``) or
    left as it is — times ``scale``."""
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    if bias is None:
        gate, index = jax.lax.top_k(scores, top_k)
    else:
        _, index = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        gate = jnp.take_along_axis(scores, index, axis=-1)
    if renormalise:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    return gate * scale, index


@jax.named_scope("experts")
def routed_experts_apply(
    moe: Dict,
    y: jax.Array,
    *,
    n_routed: int,
    top_k: int,
    scale: float,
    first_held: int = 0,
    scoring: str = "softmax",
    renormalise: bool = False,
    live: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The part of a routed expert layer that THIS device computes.

    ``y`` [n, d].  The router (``moe["router"]`` [d, n_routed + n_zero],
    with ``moe["bias"]`` [n_routed + n_zero] where the model has a choice
    bias) keeps every output, and :func:`router_choices` turns a row's
    float32 outputs into its ``top_k`` choices and their weights.
    Experts ``>= n_routed`` are zero-compute: they return their input,
    so all of a token's identity choices are ONE weighted add.  Of the
    routed experts this device holds ``moe["w_gate"].shape[0]`` from
    ``first_held`` on (SwiGLU, ``w_gate``/``w_up`` [e, d, f], ``w_down``
    [e, f, d]); a choice of an expert held elsewhere adds nothing here —
    its owner adds it, and no code stands in for that exchange.  A row
    that ``live`` [n] says is dead (an idle lane, a chunk's padding)
    chooses nothing: it is in no tile, reads no expert and is in no
    count, and its output is 0.

    Nothing is dropped and nothing is padded to a capacity: the
    assignments to held experts are grouped by expert into tiles of
    ``expert_tile_rows(n)`` rows (an expert's last tile is padded), and
    a loop runs as many tiles as the routing made, each gathering its
    rows, running its expert, and adding the weighted result to its
    rows.  The work follows the routing — an expert that no row chose
    is not read.  A row's result depends on that row alone: routing is
    per row, and a row's choices are added in expert order.

    Returns (out [n, d] in ``y``'s dtype, counts int32[6] in the order
    of ``ROUTING_COUNTS``): assignments to held, zero-compute and absent
    experts (they add up to ``top_k`` times the live rows), the held
    experts that got at least one row, the tiles the loop ran and their
    rows, padding included.  Forward only (the loop's length is data).
    """
    n, d = y.shape
    e_held = moe["w_gate"].shape[0]
    with jax.named_scope("router"):
        # bf16 x bf16 products are exact in float32, so this IS the
        # float32 product of the values the block holds
        logits = jnp.dot(y, moe["router"].astype(y.dtype),
                         preferred_element_type=jnp.float32)
        gate, index = router_choices(
            logits, moe.get("bias"), top_k=top_k, scale=scale,
            scoring=scoring, renormalise=renormalise)  # [n, k]
    chose = jnp.ones((n, 1), bool) if live is None else live[:, None]
    local = index - first_held
    held = chose & (local >= 0) & (local < e_held) & (index < n_routed)
    zero = chose & (index >= n_routed)
    y32 = y.astype(jnp.float32)
    out = jnp.sum(jnp.where(zero, gate, 0.0), -1, keepdims=True) * y32

    # group the held assignments by expert, in tiles
    tile = expert_tile_rows(n)
    a = n * top_k
    flat_local = jnp.where(held, local, e_held).reshape(a)
    flat_token = jnp.repeat(jnp.arange(n, dtype=jnp.int32), top_k)
    onehot = flat_local[:, None] == jnp.arange(e_held)[None, :]  # [a, e]
    rank = jnp.sum(jnp.where(onehot, jnp.cumsum(onehot, axis=0) - 1, 0), -1)
    counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)  # [e]
    tiles = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    # a token chooses an expert at most once: counts <= n, their sum
    # <= n * min(top_k, e_held)
    max_tiles = min(e_held * -(-n // tile),
                    n * min(top_k, e_held) // tile + e_held)
    slots = max_tiles * tile
    dest = jnp.where(
        flat_local < e_held,
        (tile_end - tiles)[jnp.minimum(flat_local, e_held - 1)] * tile + rank,
        slots)
    slot_token = jnp.full((slots,), n, jnp.int32).at[dest].set(
        flat_token, mode="drop")
    slot_gate = jnp.zeros((slots,), jnp.float32).at[dest].set(
        gate.reshape(a), mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(max_tiles), side="right"),
        e_held - 1)

    dtype = y.dtype
    rows_of = jnp.concatenate([y, jnp.zeros((1, d), dtype)])  # row n: pad

    def run_tile(i, acc):
        token = jax.lax.dynamic_slice(slot_token, (i * tile,), (tile,))
        weight = jax.lax.dynamic_slice(slot_gate, (i * tile,), (tile,))
        expert = tile_expert[i]
        rows = rows_of[token]  # [tile, d]; pad slots read the zero row
        pick = lambda w: jax.lax.dynamic_index_in_dim(
            w, expert, keepdims=False).astype(dtype)
        hidden = jax.nn.silu(rows @ pick(moe["w_gate"])) \
            * (rows @ pick(moe["w_up"]))
        result = jnp.dot(hidden, pick(moe["w_down"]),
                         preferred_element_type=jnp.float32)
        return acc.at[token].add(weight[:, None] * result)

    acc = jnp.concatenate([out, jnp.zeros((1, d), jnp.float32)])
    n_tiles = tile_end[-1].astype(jnp.int32)
    acc = jax.lax.fori_loop(0, n_tiles, run_tile, acc)
    n_held = jnp.sum(held, dtype=jnp.int32)
    n_zero = jnp.sum(zero, dtype=jnp.int32)
    n_chose = jnp.sum(chose, dtype=jnp.int32) * top_k
    stats = jnp.stack([n_held, n_zero, n_chose - n_held - n_zero,
                       jnp.sum(counts > 0, dtype=jnp.int32),
                       n_tiles, n_tiles * tile])
    return acc[:n].astype(dtype), stats


def moe_sharding_rules(ep_axis: str = "dp") -> Dict[str, P]:
    """Expert weights sharded over the expert-parallel axis (conventionally
    laid over dp); router replicated."""
    return {
        "w_in": P(ep_axis, None, None),
        "w_out": P(ep_axis, None, None),
        "router": P(),
    }
