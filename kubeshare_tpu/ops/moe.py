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

import functools
import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
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
# share of the layer that THIS device computes.  The assignments to held
# experts are grouped into tiles — one expert over at most a tile's rows —
# and the tiles are computed by ONE Pallas kernel on a TPU
# (grouped_experts: the next tile's expert copied while this one
# multiplies) or, elsewhere and where the shapes do not fit, by a
# fori_loop; expert_path chooses, from the backend and the shapes
# ---------------------------------------------------------------------------

EXPERT_TILE = 128  # rows of one grouped-matmul tile, at most
MIN_TILE = 16  # and at least: whole sublane tiles of a 2-byte row
ROW_GROUP = 8  # rows the kernel moves between unrolled steps: a sublane tile
# bytes the two copies of an expert's blocks (the one multiplied, the next
# in flight) may take of fast memory: 128 MiB on a v5e, less what the
# rows, the results and the compiler's temporaries need
EXPERT_VMEM_BYTES = 40 << 20
KERNEL_VMEM_BYTES = 112 << 20  # and all the kernel may ask for

# what routed_experts_apply counts: router choices by where the chosen
# expert lives (held here, zero-compute, held elsewhere), the held experts
# that got at least one row, and the tiles it computed with their rows
# (an expert's last tile is padded, so tile_rows >= held)
ROUTING_COUNTS = ("held", "zero", "absent", "touched", "tiles", "tile_rows")


def expert_tile_rows(n: int, top_k: int, outputs: int) -> int:
    """Rows a tile, from the call's shapes: ``n`` rows that each choose
    ``top_k`` of the router's ``outputs`` send an expert ``n * top_k /
    outputs`` rows if they choose evenly; a tile holds twice that, as a
    power of two from ``MIN_TILE`` to ``EXPERT_TILE`` (a 128-row pass
    over 128 experts sends each 8 rows: tiles of 16, not of 128).  A
    tile is one expert's, and twice the expected rows keeps an expert's
    rows in ONE tile nearly always.  In the loop a tile's padding is
    multiplied, gathered and scattered (19.8 us a tile of 16 against
    30.5 us a tile of 128 for the same 8 rows on a v5e: PERF.md, PR
    39).  In the kernel a tile's rows cost next to nothing — the matrix
    unit loads the expert's matrices once a tile whatever the rows, and
    only live rows are moved: 13.2 us a tile at 16, 32 and 128 rows
    alike — and a second tile of an expert costs its multiplications
    again (2.8 us), not a second read; there the tile only keeps the
    grid, the slots and the padding the counts report near the rows
    there are."""
    expected = -(-max(n, 1) * top_k // outputs)
    return min(EXPERT_TILE,
               max(MIN_TILE, 1 << (2 * expected - 1).bit_length()))


def expert_width_block(moe: Dict) -> int:
    """Columns of an expert's width (``f`` of ``w_gate`` [e, d, f]) a
    grid step of the kernel holds: the whole width where two copies of
    the three matrices fit ``EXPERT_VMEM_BYTES`` (9.44 MB an expert at d
    2048 x f 768), else the largest block of whole 128-lane registers
    that divides the width and fits (512 of 2048 at d 6144); 0 where
    none does or the widths do not tile."""
    _, d, f = moe["w_gate"].shape
    if d % 128 or f % 128:
        return 0
    size = jnp.dtype(moe["w_gate"].dtype).itemsize
    fits = [b for b in range(128, f + 1, 128)
            if f % b == 0 and 2 * 3 * d * b * size <= EXPERT_VMEM_BYTES]
    return max(fits, default=0)


def _kernel_vmem_bytes(moe: Dict, n: int, tile: int) -> int:
    """Fast memory :func:`grouped_experts` asks for over ``n`` rows."""
    _, d, _ = moe["w_gate"].shape
    size = jnp.dtype(moe["w_gate"].dtype).itemsize
    return (2 * 3 * d * expert_width_block(moe) * size  # the matrices, twice
            + 4 * n * d * 4  # the rows and the result, two buffers each
            + 2 * tile * d * 4  # a tile's rows and its result
            + (16 << 20))  # hidden, products, the compiler's own


def expert_kernel_fits(moe: Dict, n: int) -> bool:
    """Whether :func:`grouped_experts` can run these experts over ``n``
    rows: SwiGLU matrices of one dtype whose widths are whole 128-lane
    registers, of which some block fits fast memory twice
    (:func:`expert_width_block`) beside the ``n`` rows and their result,
    which stay there for the whole grid (``KERNEL_VMEM_BYTES``: a chunk
    of 512 rows at d 6144 does, one of 4096 at d 2048 does not).  From
    shapes alone; a tile has at most ``EXPERT_TILE`` rows."""
    shapes = {moe[k].shape for k in ("w_gate", "w_up")}
    e, d, f = moe["w_gate"].shape
    return (len(shapes) == 1 and moe["w_down"].shape == (e, f, d)
            and len({moe[k].dtype for k in ("w_gate", "w_up", "w_down")}) == 1
            and expert_width_block(moe) > 0
            and _kernel_vmem_bytes(moe, n, EXPERT_TILE) <= KERNEL_VMEM_BYTES)


def expert_path(moe: Dict, n: int, kernel_mode: Optional[str]) -> str:
    """What runs the tiles of an expert layer over ``n`` rows, chosen
    from what the program can see, as ``serving.paged.attend_path``
    chooses the attention: "kernel" (:func:`grouped_experts`) where the
    backend can run one (``kernel_mode``: ``serving.paged._kernel_mode()``
    where the program is being built) and the shapes fit it, else "loop"
    (the ``fori_loop`` over tiles).  The engine names it on its launch
    spans (``experts``)."""
    return "kernel" if kernel_mode and expert_kernel_fits(moe, n) else "loop"


# the experts' gate activation a configuration may name
# (TransformerConfig.expert_activation), the kernel's and the loop's alike
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _experts_kernel(expert_ref, rows_ref, n_tiles_ref, token_ref, x_ref,
                    gate_ref, wg_ref, wu_ref, wd_ref, o_ref, tile_ref,
                    result_ref, *, dtype, activation="silu"):
    step, block = pl.program_id(0), pl.program_id(1)
    tile = tile_ref.shape[0]
    f32 = jnp.float32

    @pl.when((step == 0) & (block == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def each_row(move):
        """``move(r, token)`` for the tile's live rows, a group at a
        time (the surplus of the last group are pad slots: row 0's
        token, weight 0)."""
        def group(g, _):
            for k in range(ROW_GROUP):
                r = g * ROW_GROUP + k
                move(r, token_ref[step * tile + r])
            return 0

        jax.lax.fori_loop(0, (rows_ref[step] + ROW_GROUP - 1) // ROW_GROUP,
                          group, 0)

    # a grid step past the live tiles computes nothing (and, by the index
    # maps, copies nothing)
    @pl.when(step < n_tiles_ref[0])
    def _():
        @pl.when(block == 0)
        def _():
            def gather(r, token):
                tile_ref[pl.ds(r, 1), :] = x_ref[pl.ds(token, 1), :]

            each_row(gather)

        # the loop's mathematics, rounded where its program on the chip
        # rounds: the products accumulated in float32 and rounded to the
        # rows' dtype (a dot's result), the activation and the product of the two
        # in float32 (the vector unit has no narrower arithmetic, and
        # XLA's fused body keeps float32 there too), the hidden rounded
        # once; the down product and the weight in float32
        x = tile_ref[...].astype(dtype)

        def product(w_ref):
            wide = jnp.dot(x, w_ref[...].astype(dtype),
                           preferred_element_type=f32)
            return wide.astype(dtype).astype(f32)

        hidden = (ACTIVATIONS[activation](product(wg_ref))
                  * product(wu_ref)).astype(dtype)
        part = gate_ref[...] * jnp.dot(hidden, wd_ref[...].astype(dtype),
                                       preferred_element_type=f32)

        @pl.when(block == 0)
        def _():
            result_ref[...] = part

        @pl.when(block > 0)
        def _():
            result_ref[...] += part

        @pl.when(block == pl.num_programs(1) - 1)
        def _():
            def add(r, token):
                o_ref[pl.ds(token, 1), :] += result_ref[pl.ds(r, 1), :]

            each_row(add)


@functools.partial(jax.jit, static_argnames=("tile", "dtype", "interpret",
                                             "activation"),
                   inline=True)
def grouped_experts(x, slot_token, slot_gate, tile_expert, tile_rows, n_tiles,
                    w_gate, w_up, w_down, *, tile: int, dtype,
                    interpret: bool = False, activation: str = "silu"):
    """Every live tile's expert over its rows, added up a row, as ONE
    Pallas TPU kernel.  ``x`` [n, d] float32 (values of ``dtype``, the
    rows' own: a row of 32-bit words can be picked by its index);
    ``slot_token`` [slots] the row each slot of a tile holds and
    ``slot_gate`` [slots] float32 its weight (a pad slot: any row, weight
    0), a tile's ``tile`` slots side by side; ``tile_expert`` /
    ``tile_rows`` [slots / tile] the expert and the live rows of each
    tile, ``n_tiles`` how many tiles are live.  Returns [n, d] float32:
    row t the sum over its slots s of ``slot_gate[s] * (act(x_t Wg) *
    (x_t Wu)) Wd`` under the slot's tile's expert, in tile order
    (``activation``: "silu" or "relu", ``ACTIVATIONS``).

    A grid over tiles, and over blocks of the expert's width where two
    copies of the whole expert do not fit fast memory
    (:func:`expert_width_block`).  The tiles' experts, rows and tokens
    are prefetched scalars and the matrices' index maps pick the expert
    from them, so the pipeline copies tile i + 1's expert while tile i
    multiplies; a second tile of the same expert finds it there (with
    the width whole), and a grid step past ``n_tiles`` names the last
    live tile's blocks again: it copies nothing and computes nothing.
    The rows and the result stay in fast memory for the whole grid: a
    tile picks its live rows from there and adds its weighted rows
    there, so nothing but the matrices moves a tile, and no accumulator
    is carried through HBM.  Jitted to be traced once for all the layers
    of a step program and inlined, as the paged attention kernels are."""
    n, d = x.shape
    slots = slot_token.shape[0]
    f = w_gate.shape[2]
    width = expert_width_block({"w_gate": w_gate})
    blocks = f // width

    def live(i, n_ref):  # an idle step stays on the last live tile
        return jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))

    def block_of(i, j, n_ref):  # ... and on its last block
        return jnp.where(i < n_ref[0], j, blocks - 1)

    def whole(i, j, *_):
        return 0, 0

    def a_tile(i, j, expert_ref, rows_ref, n_ref, token_ref):
        return live(i, n_ref), 0

    def columns(i, j, expert_ref, rows_ref, n_ref, token_ref):
        return expert_ref[live(i, n_ref)], 0, block_of(i, j, n_ref)

    def rows_of_down(i, j, expert_ref, rows_ref, n_ref, token_ref):
        return expert_ref[live(i, n_ref)], block_of(i, j, n_ref), 0

    ints = lambda a: a.astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_experts_kernel, dtype=dtype,
                          activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(slots // tile, blocks),
            in_specs=[pl.BlockSpec((n, d), whole),
                      pl.BlockSpec((tile, 1), a_tile),
                      pl.BlockSpec((None, d, width), columns),
                      pl.BlockSpec((None, d, width), columns),
                      pl.BlockSpec((None, width, d), rows_of_down)],
            out_specs=pl.BlockSpec((n, d), whole),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32),
                            pltpu.VMEM((tile, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_kernel_vmem_bytes(
                {"w_gate": w_gate}, n, tile)),
        interpret=interpret,
        name="grouped_experts",
    )(ints(tile_expert), ints(tile_rows),
      jnp.reshape(n_tiles, (1,)).astype(jnp.int32), ints(slot_token), x,
      slot_gate[:, None], w_gate, w_up, w_down)


def router_choices(logits: jax.Array, bias: Optional[jax.Array], *,
                   top_k: int, scale: float, scoring: str = "softmax",
                   renormalise: bool = False,
                   renormalise_eps: float = 1e-20
                   ) -> Tuple[jax.Array, jax.Array]:
    """The router's law: a row's float32 ``logits`` [n, outputs] ->
    (``gate`` [n, top_k] float32, ``index`` [n, top_k]).

    A row's scores are the softmax over all its outputs (``scoring``
    "softmax") or each output's sigmoid ("sigmoid").  It chooses the
    ``top_k`` largest of ``scores + bias``: the bias (None: none) moves
    the CHOICE only and never the weight, which is the chosen expert's
    own score — renormalised over the chosen ones (``renormalise``: each
    over their sum + ``renormalise_eps``) or left as it is — times
    ``scale``."""
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
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True)
                       + renormalise_eps)
    return gate * scale, index


def _route(moe: Dict, y: jax.Array, **law) -> Tuple[jax.Array, jax.Array]:
    """The router of an expert layer over the rows it reads, ``y`` [n, d]:
    its float32 outputs (``moe["router"]`` [d, outputs], with ``moe["bias"]``
    where the model has a choice bias) turned into each row's choices by
    ``law`` (:func:`router_choices`' keywords) -> (``gate``, ``index``),
    both [n, top_k]."""
    # bf16 x bf16 products are exact in float32, so this IS the
    # float32 product of the values the block holds
    logits = jnp.dot(y, moe["router"].astype(y.dtype),
                     preferred_element_type=jnp.float32)
    return router_choices(logits, moe.get("bias"), **law)


# the router called apart from its experts (a layer whose router reads the
# layer's input chooses before the attention), under the scopes it has
# inside :func:`routed_experts_apply`: stage `experts` keeps it
route = jax.named_scope("experts")(jax.named_scope("router")(_route))


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
    renormalise_eps: float = 1e-20,
    live: Optional[jax.Array] = None,
    kernel_mode: Optional[str] = None,
    choices: Optional[Tuple[jax.Array, jax.Array]] = None,
    activation: str = "silu",
) -> Tuple[jax.Array, jax.Array]:
    """The part of a routed expert layer that THIS device computes.

    ``y`` [n, d].  The router (``moe["router"]`` [d, n_routed + n_zero],
    with ``moe["bias"]`` [n_routed + n_zero] where the model has a choice
    bias) keeps every output, and :func:`router_choices` turns a row's
    float32 outputs into its ``top_k`` choices and their weights
    (:func:`_route`; ``choices``, where the router read other rows than
    ``y`` — the layer's input, say — are what it made of those).
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
    :func:`expert_tile_rows` rows (an expert's last tile is padded), and
    as many tiles are computed as the routing made, each running its
    expert over its rows.  The work follows the routing — an expert that
    no row chose is not read.  That much is one code for every backend;
    what computes the tiles is :func:`expert_path`'s choice.  "kernel",
    where ``kernel_mode`` (how a Pallas kernel can run where the program
    is being built: "compiled" on a TPU, "interpret" under a test, None
    elsewhere) and the experts' shapes allow: :func:`grouped_experts`,
    one kernel over all the tiles with the next expert's copy in flight,
    the rows and the result resident in fast memory.  "loop", everywhere
    else: a ``fori_loop`` over the tiles, each gathering its rows,
    picking its expert and adding its weighted rows into an accumulator
    the loop carries.  A row's result depends on that row alone on
    either path: routing is per row, and a row's choices are added in
    expert order.

    Returns (out [n, d] in ``y``'s dtype, counts int32[6] in the order
    of ``ROUTING_COUNTS``): assignments to held, zero-compute and absent
    experts (they add up to ``top_k`` times the live rows), the held
    experts that got at least one row, the tiles computed and their
    rows, padding included.  Forward only (the tiles' count is data).
    """
    n, d = y.shape
    e_held = moe["w_gate"].shape[0]
    if choices is None:
        with jax.named_scope("router"):
            choices = _route(moe, y, top_k=top_k, scale=scale,
                             scoring=scoring, renormalise=renormalise,
                             renormalise_eps=renormalise_eps)
    gate, index = choices  # [n, k]
    chose = jnp.ones((n, 1), bool) if live is None else live[:, None]
    local = index - first_held
    held = chose & (local >= 0) & (local < e_held) & (index < n_routed)
    zero = chose & (index >= n_routed)
    y32 = y.astype(jnp.float32)
    out = jnp.sum(jnp.where(zero, gate, 0.0), -1, keepdims=True) * y32

    # group the held assignments by expert, in tiles
    tile = expert_tile_rows(n, top_k, moe["router"].shape[1])
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
    # tile i is the expert's whose tiles end after it: how many end by i
    tile_expert = jnp.minimum(
        jnp.sum(tile_end[None, :] <= jnp.arange(max_tiles)[:, None], axis=1),
        e_held - 1)
    n_tiles = tile_end[-1].astype(jnp.int32)

    dtype = y.dtype
    if expert_path(moe, n, kernel_mode) == "kernel":
        first_tile = tile_end - tiles  # of each expert
        at = jnp.arange(max_tiles) - first_tile[tile_expert]
        tile_rows = jnp.clip(counts[tile_expert] - at * tile, 0, tile)
        out = out + grouped_experts(
            y32, jnp.minimum(slot_token, n - 1), slot_gate, tile_expert,
            tile_rows, n_tiles, moe["w_gate"], moe["w_up"], moe["w_down"],
            tile=tile, dtype=dtype, interpret=kernel_mode == "interpret",
            activation=activation)
    else:
        rows_of = jnp.concatenate([y, jnp.zeros((1, d), dtype)])  # n: pad

        def run_tile(i, acc):
            token = jax.lax.dynamic_slice(slot_token, (i * tile,), (tile,))
            weight = jax.lax.dynamic_slice(slot_gate, (i * tile,), (tile,))
            expert = tile_expert[i]
            rows = rows_of[token]  # [tile, d]; pad slots read the zero row
            pick = lambda w: jax.lax.dynamic_index_in_dim(
                w, expert, keepdims=False).astype(dtype)
            hidden = ACTIVATIONS[activation](rows @ pick(moe["w_gate"])) \
                * (rows @ pick(moe["w_up"]))
            result = jnp.dot(hidden, pick(moe["w_down"]),
                             preferred_element_type=jnp.float32)
            return acc.at[token].add(weight[:, None] * result)

        acc = jnp.concatenate([out, jnp.zeros((1, d), jnp.float32)])
        out = jax.lax.fori_loop(0, n_tiles, run_tile, acc)[:n]
    n_held = jnp.sum(held, dtype=jnp.int32)
    n_zero = jnp.sum(zero, dtype=jnp.int32)
    n_chose = jnp.sum(chose, dtype=jnp.int32) * top_k
    stats = jnp.stack([n_held, n_zero, n_chose - n_held - n_zero,
                       jnp.sum(counts > 0, dtype=jnp.int32),
                       n_tiles, n_tiles * tile])
    return out.astype(dtype), stats


def moe_sharding_rules(ep_axis: str = "dp") -> Dict[str, P]:
    """Expert weights sharded over the expert-parallel axis (conventionally
    laid over dp); router replicated."""
    return {
        "w_in": P(ep_axis, None, None),
        "w_out": P(ep_axis, None, None),
        "router": P(),
    }
