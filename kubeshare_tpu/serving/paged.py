"""Paged twins of the dense cached model steps.

``models/decoding._decode_chunk`` reads and writes a dense
[layers, batch, h_kv, max_seq, d] cache whose rows advance in lockstep
(one scalar length for the whole batch).  Serving needs neither
property: each slot sits at its OWN length, and its cache rows live
scattered across pool blocks (kv_blocks.py).  The two entry points here
keep the dense step's exact math — same projections, same rope, same
per-query causal band (through the SAME :func:`_attend_cached`, or its
blockwise twin over a long view) — and swap only the cache plumbing.
That plumbing is two functions over the stacked
pool ``[n_layers, num_blocks, h_kv, block_size, d]`` (or, for a block that
caches a latent row, the layout ``kv_blocks.KVRowLayout`` gives), which
every step program receives as its donated arguments: :func:`_write_rows` scatters a
layer's new K/V rows into that buffer itself, and :func:`_layer_reader`
gathers a layer's per-lane views from that layer's window of it through
the block tables.  No step scatters into a slab cut out of the pool or
builds a new pool from per-layer pieces, so no program holds a second
pool: a step moves the rows it writes and what its views read
(``tests/test_serving.py::TestPoolWrittenInPlace`` pins the structure,
``tests/test_chip_compile.py`` what the TPU compiler makes of it).

What the views read follows what the lanes HOLD, not what a lane may
hold: a view longer than one key block (``KEY_BLOCK`` rows) is gathered
and attended a key block at a time, the softmax carried across the
blocks, and the loop stops where the furthest lane's last row lies
(:func:`_attend_view` for the dense block, ``latent_attend_blocks`` for
the latent ones; both carry the one running softmax,
``models/transformer.attend_key_blocks``).  Where a lane's query rows
all see the same keys (the decode step's one row, a diffusion pass's one
aligned block) and the program is built for a TPU, a
Pallas kernel stands in for that loop (``ops/paged_attention``: one for
the dense block's K and V a head, one for the latent blocks' latent row
and packed rotary keys, over one shared page walk): each lane reads its
OWN pages of the pool up to its own reach, an idle lane none, and
nothing is gathered or staged for all the lanes (:func:`attend_path`
makes the choice, from shapes, the block's row layout and the backend).
The dense block's view no longer than a key block is attended whole,
through the dense step's own :func:`_attend_cached`.  The engine counts
how far each dispatch went (``view_rows_reached`` against
``view_rows_configured``, ``view_rows_held`` for the decode lanes' own
rows) and names what its decode lanes ran on the launch span
(``attend``).

The layers themselves are written once a block kind (:func:`_dense_layers`,
:func:`_gqa_moe_layers`, :func:`_latent_layers`, :func:`_retention_layers`)
and run by every step through :func:`_run_layers`.  A model whose lanes hold
a state BY SLOT beside the pool — a 'retention' block's recurrent states, the
short convolutions' windows of a model whose layers name their operator —
carries it through every step program in a :class:`Recurrent`, donated like
the pool, and every step returns its outputs, then a routed block's routing
counts, then the :class:`Recurrent` (:func:`_step_outputs`).  The entry
points:

- :func:`paged_prefill_step`: a width-C prompt chunk writing its K/V
  straight into a slot's blocks (no dense staging cache to copy from);
- :func:`paged_decode_step`: one token for EVERY active slot at once —
  per-slot positions, scatter-write each slot's K/V into its current
  block, gather each slot's block list into a [S, h_kv, V, d] view (a
  key block of it at a time when it is long), and attend under per-row
  causal bands;
- :func:`paged_decode_span`: the multi-token decode dispatch — a
  ``lax.scan`` of step-identical :func:`paged_decode_step` iterations
  with the engine's token-pick policy between steps (lanes
  self-deactivate on budget/EOS);
- :func:`paged_decode_loop`: the device-resident multi-step loop — up
  to K consecutive span-units (each one the EXACT span scan above)
  inside a ``lax.while_loop``, emissions ring-buffered on device and
  an early exit at span boundaries the moment any lane deactivates
  (the host's cue that the lane set changed and scheduling must run);
- :func:`paged_mixed_step`: the stall-free mixed dispatch — ONE program
  that consumes one bounded prefill chunk for one filling slot AND runs
  a full decode span for every active lane.  The chunk rides the span's
  FIRST pass over the weights: its rows and the lanes' first rows go
  through one layer loop side by side (:func:`_mixed_first_step`), the
  attention alone a group at a time (:func:`_attend_rows`), and the scan
  over the span's other steps follows — ``span`` passes over the weights a
  dispatch, not ``span + 1``.  The prefill lane's blocks are disjoint from
  every decode lane's writable blocks (shared prefix blocks are read-only
  to both — divergence is copied-on-write before any append), so both
  groups' rows are written before either attends and neither reads a row
  the other writes.  A state by slot parts the rows where the attention
  does: the chunk's rows cross the filling slot's state in order, each
  lane's one row its own slot's, and a 'retention' block's folds stay the
  program's last phase.  The two entry points above back to back
  (:func:`paged_mixed_back_to_back`: prefill first, then the span), op for
  op the split dispatches' math, are the composition the fused step is
  held to, token for token — no engine of one chip runs it;
- :func:`paged_verify_span`: the speculative draft-verify dispatch —
  one width-W chunk scores every lane's self-drafted tokens at once,
  picks what sequential decoding would emit at each position (each
  column under its own emission's temperature/PRNG key), and counts
  the accepted prefix with the dense decoder's exact acceptance rule;
- :func:`paged_mixed_verify_step`: the speculative twin of the mixed
  dispatch (prefill chunk + verify span, one program);
- :func:`paged_diffusion_prefill`, :func:`paged_diffusion_pass`,
  :func:`paged_mixed_diffusion_step`: a configuration that generates by
  diffusion over blocks (``TransformerConfig.diffusion_block``) — a
  block-causal chunk that yields no token, one pass over every lane's
  block of B rows that see one another (the denoising pass and the
  commit pass are the one program, with the pick, its confidence and the
  choice of rows to commit on the device), and the two in one program.

Equivalence with the dense cache is test-locked (tests/test_serving.py):
greedy and sampled streams from the paged pool match ``init_kv_cache``
decoding exactly, GQA and windowed configs included.

Inactive-slot lanes still execute under jit (static shapes); their
writes are routed to the reserved scratch block 0 and their outputs
ignored host-side.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.decoding import (
    _attend_blocks,
    _attend_cached,
    _check_moe_decodable,
    speculative_acceptance,
)
from ..models.transformer import (TransformerConfig, _rms_norm,
                                  attend_reach, gqa_moe_layers, gqa_qkv,
                                  latent_absorbed, latent_context_blocks,
                                  latent_layers, latent_qkv, latent_scale,
                                  retention_gate, retention_layers,
                                  retention_qkv)
from ..ops.moe import ROUTING_COUNTS, expert_path
from ..ops.retention import (fold_update, retention_output, state_sums,
                             tail_sums)
from ..ops.paged_attention import (kernel_fits, latent_kernel_fits,
                                   paged_decode_attention,
                                   paged_latent_decode_attention)
from ..ops.rope import apply_rope
from ..ops.short_conv import (conv_filter, conv_gates, conv_out,
                              state_after)
from .drafter import ngram_propose_rows


@jax.named_scope("kv_view")
def paged_gather_kv(pool_k, pool_v, block_table):
    """Materialize one slot's virtual K/V view.

    ``pool_k``/``pool_v``: [n_layers, num_blocks, h_kv, bs, d];
    ``block_table``: [T] int32.  Returns (k, v) each
    [n_layers, h_kv, T*bs, d] — virtual position p at row p (block
    ``table[p // bs]``, offset ``p % bs``).
    """
    n_layers, _, h_kv, bs, d = pool_k.shape
    t = block_table.shape[0]

    def view(pool):
        blocks = pool[:, block_table]  # [L, T, h_kv, bs, d]
        return blocks.transpose(0, 2, 1, 3, 4).reshape(n_layers, h_kv, t * bs, d)

    return view(pool_k), view(pool_v)


def paged_copy_block(pool_k, pool_v, src, dst):
    """Copy ONE block's rows (all layers, K and V) ``src`` -> ``dst`` —
    the prefix cache's copy-on-write primitive.

    A partially filled cached block cannot be appended to in place: its
    tail rows are shared state (other slots read them; the trie indexes
    them), so a request whose prompt diverges mid-block gets a private
    copy and writes there.  ``src``/``dst`` ride as TRACED scalars, so
    the jitted copy compiles exactly once (block shape is static) —
    warmup covers it and the zero-recompile property holds with the
    cache enabled.  All ``block_size`` rows are copied: rows past the
    matched prefix are stale, but prefill overwrites them before any
    causal band can reach them (the same write-then-attend order that
    makes pad rows dead in the chunked prefill).
    """
    return (pool_k.at[:, dst].set(pool_k[:, src]),
            pool_v.at[:, dst].set(pool_v[:, src]))


def paged_upload_block(pool_k, pool_v, dst, k_slab, v_slab):
    """Write ONE block's rows (all layers, K and V) from host slabs —
    the KV tier's promotion primitive (kv_tier.py).

    ``k_slab``/``v_slab`` are a demoted block's deserialized payload,
    shape [n_layers, kv_heads, block_size, head_dim]; ``dst`` rides as
    a TRACED scalar so the jitted upload compiles exactly once (the
    slab shape is static — one block, like ``paged_copy_block``), and
    warmup covers it: promotion adds ZERO compiled shapes after the
    warmed one.  Rows past the payload's filled token count are the
    demoted block's stale tail; prefill overwrites them before any
    causal band can attend (the same write-then-attend order that makes
    the CoW copy's surplus rows dead).
    """
    return (pool_k.at[:, dst].set(k_slab),
            pool_v.at[:, dst].set(v_slab))


@jax.named_scope("kv_write")
def _write_rows(pool_k, pool_v, layer_idx, blk, off, k, v):
    """Scatter one layer's new K/V rows into the stacked pool itself:
    rows ``[layer_idx, blk[...], :, off[...], :] <- k[..., :, :]``
    (``blk``/``off`` [...] int32, ``k``/``v`` [..., h_kv, d]).  The one
    pool write every paged step goes through.  The pool is the step
    program's donated argument, so the scatter updates that buffer in
    place and the rows are the only bytes of the pool a step writes —
    no slab is written apart from the pool, and nothing is restacked.

    The KV head is an index of the scatter, not a slice of it: the
    update window is one row of ``d``.  With the ``[h_kv, d]`` window
    that ``[layer_idx, blk, :, off, :]`` gives, the TPU compiler re-tiles
    the whole pool so that heads sit next to rows whenever ``h_kv > 1``
    — two pool-sized copies in and two out, every program (PERF.md,
    PR 25).

    Nor is the window ever narrower than the array's row.  Where ``v``
    is a part of a packed V row (:func:`_v_part`: the array's row is
    wider than ``v``), the rows are read, ``v`` is laid into its lanes
    and the WHOLE rows are scattered back, so the other lanes keep what
    they held (the pair's other key, or the page's old contents).
    Scattered through the part's own 64-wide window the TPU compiler
    expands the scatter into a ``while`` of one ``dynamic-update-slice``
    of the pool an update: 4.5-5.0 us a row on a v5e against 0.09 us
    through the whole row, a quarter of a latent cell's dispatch
    (PERF.md, PR 42)."""
    if blk.ndim == 2 and blk.shape[1] == 1:
        # a decode step's one row a lane: the scatter it always was
        blk, off, k, v = blk[:, 0], off[:, 0], k[:, 0], v[:, 0]
    blk, off = blk[..., None], off[..., None]
    v_layer, lanes = _v_part(pool_v, layer_idx, v.shape[-1])
    if pool_v.shape[-1] != v.shape[-1]:
        # dead rows that share one (blk, off) all read the same other half
        held = pool_v[v_layer, blk, jnp.arange(pool_v.shape[2]), off]
        v, lanes = held.at[..., lanes].set(v), slice(None)
    return (pool_k.at[layer_idx, blk, jnp.arange(pool_k.shape[2]), off,
                      :].set(k),
            pool_v.at[v_layer, blk, jnp.arange(pool_v.shape[2]), off,
                      lanes].set(v))


def _v_part(pool_v, layer_idx, width: Optional[int] = None):
    """Where pool layer ``layer_idx``'s V row of ``width`` values (None:
    the array's whole row) lies: the V array's layer and the lanes of
    its row.  The V rows of ``packed`` consecutive layers share one
    array row (kv_blocks.KVRowLayout ``v_packed``; 1, the whole row, for
    a K and a V a head); an odd count of layers leaves the last row's
    second half spare.  The lanes are for READING a part out of rows
    already gathered (:func:`_layer_reader`); a write goes through the
    whole row all the same (:func:`_write_rows`: a scatter whose window
    is the part becomes a loop of one update a row on the TPU)."""
    packed = 1 if width is None else pool_v.shape[-1] // width
    if packed == 1:
        return layer_idx, slice(None)
    lo = layer_idx % packed * width
    return layer_idx // packed, slice(lo, lo + width)


# A layer's slab larger than this is not windowed out of the pool before
# the gather (see _layer_reader): it cannot be staged in fast memory, and
# the window becomes a copy of the whole slab in HBM.  Measured on a v5e
# (PERF.md, PR 27): 179 MB of latent rows, a decode span of 4 steps 134.7
# ms with the window and 117.4 without; 44.7 MB of rotary keys, gathered
# a key block at a time, 75.2 ms with and 66.3 without.  The dense cells'
# slabs, 33.5 and 17.9 MB, are staged (PR 25).
STAGED_SLAB_MAX_BYTES = 40 << 20


def _layer_reader(pool_k, pool_v, layer_idx, lanes: int, table_width: int,
                  v_width: Optional[int] = None):
    """The read path of ONE layer of the stacked pool [L, B, h_kv, bs, d]
    (``v_width``: the width of the layer's own V row where several
    layers' share one array row, see :func:`_v_part`)
    for ``lanes`` lanes whose tables are ``table_width`` entries wide:
    returns ``views(tables [lanes, T]) -> (k, v)``, each lane's virtual
    view [lanes, h_kv, T*bs, d] gathered through ``T <= table_width`` of
    its table entries; the head count is the pool's own, so a
    head-sharded pool shard (serving/sharded.py) reads through the same
    function.  The one view construction every paged step attends
    through — a change here is a change to the paged read path, full
    stop.

    ``pool[layer_idx]`` is a window at a fixed offset of the donated
    buffer, not a copy in HBM: the TPU compiler stages that layer's
    slab in the chip's fast memory when it fits there and gathers the
    blocks from the staged copy.  The window is taken HERE, once a
    layer, and ``views`` only gathers from it: a caller that asks for
    the view a key block at a time (:func:`_attend_view`) calls ``views``
    inside its loop, and a window taken in there is staged again every
    block.  Indexing layer and table in ONE gather
    (``pool[layer_idx, tables]``) reads the same blocks straight from
    HBM, 4 or 8 KB at a time, at a quarter of the bandwidth.  Staging is
    a pass over the whole slab whatever is read from it, so it is taken
    only where it can pay: the slab fits (a slab over
    ``STAGED_SLAB_MAX_BYTES`` cannot be staged, its window IS a copy in
    HBM) and the lanes' whole views could read as many blocks as the
    pool has to give — the decode lanes' gathers, not the prefill
    chunk's one lane, which reads a 256th of a slab a key block.
    Measured alone on a v5e at the cells' sizes (PERF.md, PR 28; ms, a
    mixed dispatch of a 256-token chunk and 4 decode steps; 1 B with 2
    live lanes of 32 reaching 3 key blocks / 32 lanes reaching 6, 3 B
    with 16 lanes reaching 6 / 1): the whole view at once 39.2 / 39.3,
    75.8 / 75.8; the window taken inside the loop 51.6 / 77.3, 100.9 /
    63.5; every gather from HBM 28.9 / 34.3, 71.4 / 52.5; every gather
    from the slab staged once 33.2 / 34.7, 67.0 / 60.7; this rule 27.3 /
    27.9, 63.8 / 57.1."""
    def reader(pool, layer):
        _, blocks, h_kv, bs, d = pool.shape  # K's and V's rows may differ
        slab_bytes = blocks * h_kv * bs * d * pool.dtype.itemsize
        # block 0 is the scratch block: blocks - 1 can be allocated
        if (slab_bytes <= STAGED_SLAB_MAX_BYTES
                and lanes * table_width >= blocks - 1):
            slab = pool[layer]

            def gather(tables):
                return slab[tables]
        else:
            def gather(tables):
                return pool[layer, tables]

        @jax.named_scope("kv_view")
        def view(tables):
            p, t = tables.shape
            return gather(tables).transpose(0, 2, 1, 3, 4).reshape(
                p, h_kv, t * bs, d)

        return view

    v_layer, part = _v_part(pool_v, layer_idx, v_width)
    with jax.named_scope("kv_view"):
        view_k, view_v = reader(pool_k, layer_idx), reader(pool_v, v_layer)
    return lambda tables: (view_k(tables), view_v(tables)[..., part])


def _layer_views(pool_k, pool_v, layer_idx, tables,
                 v_width: Optional[int] = None):
    """Per-lane virtual K/V views of ONE layer through lane tables [P, T]
    -> [P, h_kv, T*bs, d]: :func:`_layer_reader`, read once."""
    return _layer_reader(pool_k, pool_v, layer_idx, *tables.shape,
                         v_width)(tables)


KEY_BLOCK = 512  # view rows a step of the blockwise attention takes


def _page_rows(pool_k) -> int:
    """Rows a page of the pool holds (a pool by layer kind is an array a
    kind, ``kv_blocks.PagedKVPool``: their pages hold the same rows)."""
    return jax.tree_util.tree_leaves(pool_k)[0].shape[3]


def key_block_entries(table_width: int, block_size: int) -> int:
    """Table entries a key block: the most that divide the table's width
    and hold no more than ``KEY_BLOCK`` rows."""
    return max(e for e in range(1, table_width + 1)
               if table_width % e == 0
               and e * block_size <= max(KEY_BLOCK, block_size))


# the blocks whose cache row is a K and a V a KV head (kv_blocks.KVRowLayout
# kind "kv_heads"): they attend through :func:`_attend_view`
KV_HEADS_BLOCKS = ("dense", "gqa_moe")


def _kernel_mode():
    """How the paged kernel (``ops/paged_attention``) can run where this
    step program is being built: compiled for a TPU, not at all
    elsewhere.  A test holds the kernel to the loop off the chip by
    returning "interpret" from here."""
    return "compiled" if jax.default_backend() == "tpu" else None


def attend_path(block: str, query_rows: int, table_width: int, pool_k,
                pool_v, head_dim: int, diffusion_block: int = 0) -> str:
    """What a step's queries of ``query_rows`` rows a lane run over views
    of ``table_width`` table entries, chosen from what the program can
    see — the view's width, the query's, the block's row layout (the
    pool's shapes), the backend: "whole" (the dense block's view no
    longer than one key block, attended at once), "kernel" (a lane's
    rows all see the same keys, over a longer view, where the paged
    kernel of the block's row layout can run: each lane reads its own
    pages, bounded by its own reach) or "blocks" (the key-block loop, as
    far as the furthest lane reaches; the latent blocks run it over a
    short view too); a 'retention' block has no view: "tail"
    (:func:`_retention_layers`).  A lane's rows see the same keys where
    they are one row, or ``diffusion_block`` rows under generation by
    diffusion over blocks: every program starts a lane's rows at a multiple of B
    (:func:`paged_diffusion_pass`'s ``lengths``, the chunk's ``starts``),
    so B rows are one aligned block and ``attend_reach`` gives them all
    its last row.  A chunk of many blocks and the verify spans, whose
    rows' reaches differ, run the loop.  The engine names the path on its
    launch spans (``attend``)."""
    if block == "retention":
        return "tail"  # a window of unfolded rows beside the lane's state
    short = table_width * pool_k.shape[3] <= KEY_BLOCK
    if block in KV_HEADS_BLOCKS:
        if short:
            return "whole"
        fits = kernel_fits(pool_k, pool_v, head_dim)
    else:
        fits = not short and latent_kernel_fits(pool_k, pool_v)
    if query_rows in (1, diffusion_block) and fits and _kernel_mode():
        return "kernel"
    return "blocks"


def experts_path(moe, rows: int) -> str:
    """What a routed block's step programs compute the tiles of an expert
    layer ``moe`` over ``rows`` rows with, where they are being built:
    "kernel" or "loop" (``ops.moe.expert_path`` under
    :func:`_kernel_mode`, which ``transformer.routed_experts`` hands the
    layer).  The engine names it on its launch spans (``experts``)."""
    return expert_path(moe, rows, _kernel_mode())


def _attend_view(q, pool_k, pool_v, layer_idx, tables, positions, window,
                 diffusion_block: int = 0, scale: Optional[float] = None):
    """The dense block's attention of ``q`` [B, h, C, d] over each lane's
    view of pool layer ``layer_idx``, under the per-query causal band
    (``positions`` [B, C]: the last row each query sees).

    A view longer than one key block is attended only as far as its
    lanes hold rows.  A lane's rows that all see the same keys — one row
    (the decode step), or the ``diffusion_block`` rows of one aligned
    block (a diffusion pass), which are one query group of C x h / h_kv
    rows a KV head — go through the paged kernel where that can run:
    each lane reads its OWN pages of the pool, up to its own reach, and
    an idle lane reads none.  Queries whose rows' reaches differ (the
    prefill chunk, a verify span), and every step off the TPU, run the
    key-block loop (``_layer_reader``'s views of that part of the
    table), as far as the furthest lane reaches.  A view no longer than a
    key block is attended whole — a static shape, not a knob: a one-trip
    loop buys nothing.  ``scale`` is what the scores are multiplied by
    (None: ``q.shape[3] ** -0.5``), the same on every path."""
    path = attend_path("dense", q.shape[2], tables.shape[1], pool_k, pool_v,
                       q.shape[3], diffusion_block)
    if path == "whole":
        view_k, view_v = _layer_views(pool_k, pool_v, layer_idx, tables)
        return _attend_cached(q, view_k, view_v, positions, window=window,
                              scale=scale)
    if path == "kernel":
        return paged_decode_attention(
            q, pool_k, pool_v, layer_idx, tables, positions[:, 0],
            window=window, interpret=_kernel_mode() == "interpret",
            scale=scale)
    entries = key_block_entries(tables.shape[1], pool_k.shape[3])
    return _attend_view_blocks(q, pool_k, pool_v, layer_idx, tables,
                               positions, entries, window, scale)


@functools.partial(jax.jit, static_argnames=("entries", "window", "scale"),
                   inline=True)
def _attend_view_blocks(q, pool_k, pool_v, layer_idx, tables, positions,
                        entries, window, scale=None):
    """:func:`_attend_view` of a long view, ``entries`` table entries a
    key block.  Jitted to be traced ONCE for
    all the layers of a step program (the layer is an argument, and the
    shapes are the same in every layer) and inlined: the program is what
    it would be written out a layer, and ``engine.warmup()`` does not
    trace a key-block loop a layer (30 layers x 20 programs: 16 s of
    ``setup_s`` at 3 B; PERF.md, PR 28)."""
    views = _layer_reader(pool_k, pool_v, layer_idx, *tables.shape)

    def view_block(i):
        return views(jax.lax.dynamic_slice_in_dim(
            tables, i * entries, entries, axis=1))

    return _attend_blocks(q, view_block, entries * pool_k.shape[3],
                          pool_k.shape[2], positions, window, scale)


class RowGroup(NamedTuple):
    """One group of a fused step's rows (:func:`_mixed_first_step`): ``B``
    lanes of ``C`` rows each, lane-major along the step's one row axis,
    which attend ``tables`` [B, T] at ``positions`` [B, C].  Where the model
    carries a state BY SLOT (:class:`Recurrent`) a group also says whose
    states its rows cross: ``live`` [B, C] its real rows, ``slots`` [B] its
    lanes' slots (None: lane b IS slot b, the decode lanes), and — a
    'retention' block — ``folded`` [B] its lanes' fold points and ``grow``
    the rows a tail of its may gain before the next fold."""

    tables: jax.Array
    positions: jax.Array
    live: Optional[jax.Array] = None
    folded: Optional[jax.Array] = None
    slots: Optional[jax.Array] = None
    grow: int = 0


def _by_group(groups, fn, *rows):
    """``fn(group, *rows)`` for every group of a fused step's rows: each
    group's rows are cut out of ``rows`` [1, H, R, .], laid out as its lanes
    hold them [B, H, C, .], and the results [B, H, C, .] go back side by
    side [1, H, R, .].  ``groups``: anything a group that has ``positions``
    [B, C]."""
    out, lo = [], 0
    for group in groups:
        b, c = group.positions.shape

        def lanes_of(q, lo=lo):  # [1, H, R, w] -> [B, H, C, w]
            h, w = q.shape[1], q.shape[3]
            return q[0, :, lo:lo + b * c].reshape(h, b, c, w).transpose(
                1, 0, 2, 3)

        o = fn(group, *map(lanes_of, rows))
        out.append(o.transpose(1, 0, 2, 3).reshape(
            1, o.shape[1], b * c, o.shape[3]))
        lo += b * c
    return jnp.concatenate(out, axis=2)


def _attend_rows(tables, positions, attend, *queries):
    """A layer's attention, which is ``attend(tables, positions, *queries)
    -> context`` over lanes that each hold their own rows: ``tables``
    [B, T], ``positions`` [B, C], ``queries`` and the context [B, H, C, .].

    A fused step hands the layers a tuple of :class:`RowGroup` as their
    ``tables``: its rows are ONE lane [1, R] of all the groups' rows side by
    side, so everything that is a row's own (the norms, the projections,
    rope by the row's position, the row's write, the feed-forward) runs
    once over all of them, and only here do the groups part
    (:func:`_by_group`): each group's rows are attended over its own tables
    by whatever :func:`attend_path` chooses for its width, and the contexts
    go back side by side.  (A state by slot parts them the same way:
    :func:`_lane_groups`.)"""
    if not isinstance(tables, tuple):
        return attend(tables, positions, *queries)
    return _by_group(
        tables, lambda group, *q: attend(group.tables, group.positions, *q),
        *queries)


def _lane_groups(tables, positions, live, carried):
    """Whose states a step's rows cross -> (the :class:`Recurrent`, the
    step's groups of lanes).  A fused step's groups bring their own
    ``live``, ``folded``, ``slots`` and ``grow`` (``carried`` = the
    :class:`Recurrent` alone); any other step's rows are one group, which
    ``carried`` = (:class:`Recurrent`, ``folded``, ``slots``, ``grow``)
    describes."""
    if isinstance(tables, tuple):
        return carried[0], tables
    recurrent, folded, slots, grow = carried
    return recurrent, (RowGroup(tables, positions, live, folded, slots,
                                grow),)


@jax.named_scope("mlp")
def _moe_or_mlp(layer, config: TransformerConfig, y):
    """The post-attention feed-forward shared by both paged steps —
    identical contract to the dense step: MoE capacity pinned to the
    token count so routing stays position- and batch-independent (a
    co-batched slot cannot perturb another's outputs through
    expert-capacity collisions)."""
    if "moe" in layer:
        from ..ops.moe import MoEConfig, moe_apply

        _check_moe_decodable(config)
        e, d_m, f = layer["moe"]["w_in"].shape
        out, _ = moe_apply(
            layer["moe"], y,
            MoEConfig(d_model=d_m, d_ff=f, num_experts=e,
                      capacity_factor=config.moe_capacity_factor,
                      top_k=config.moe_top_k,
                      dispatch=config.moe_dispatch),
            capacity=y.shape[0] * y.shape[1],
        )
        return out.astype(config.dtype)
    hidden = jax.nn.gelu(y @ layer["mlp"]["w_in"].astype(config.dtype))
    return hidden @ layer["mlp"]["w_out"].astype(config.dtype)


def _dense_layers(params, config: TransformerConfig, pool_k, pool_v,
                  tables, positions, blk, off, x, live):
    """The dense block's layers over ``x`` [B, C, d]: lane b's C rows sit
    at virtual positions ``positions[b]`` of ``tables[b]`` and are
    written at ``(blk, off)`` [B, C] first, then attend the lane's view
    under the per-query causal band (:func:`_attend_view`).  ``live``
    [B, C] marks the rows that are real (not an idle lane's, not a
    chunk's padding); the dense block computes every row alike.  A fused
    step's ``tables`` are its row groups (:func:`_attend_rows`)."""
    dtype = config.dtype
    use_rope = config.positional == "rope"

    def attend(tables, positions, q):  # of the layer the loop is at
        return _attend_view(q, pool_k, pool_v, layer_idx, tables, positions,
                            config.attention_window).astype(dtype)

    for layer_idx, layer in enumerate(params["layers"]):
        y = _rms_norm(x, layer["norm1"]["scale"])
        with jax.named_scope("attention"):
            q = jnp.einsum("bsd,dhk->bhsk", y,
                           layer["attn"]["wq"].astype(dtype))
            k = jnp.einsum("bsd,dhk->bhsk", y,
                           layer["attn"]["wk"].astype(dtype))
            v = jnp.einsum("bsd,dhk->bhsk", y,
                           layer["attn"]["wv"].astype(dtype))
            if use_rope:
                q = apply_rope(q, positions)  # [B, C]: per-lane positions
                k = apply_rope(k, positions)
        # rows (layer, blk[b,i], :, off[b,i], :) <- k[b, :, i, :]
        pool_k, pool_v = _write_rows(
            pool_k, pool_v, layer_idx, blk, off,
            k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
        with jax.named_scope("attention"):
            o = _attend_rows(tables, positions, attend, q)
            x = x + jnp.einsum("bhsk,hkd->bsd", o,
                               layer["attn"]["wo"].astype(dtype))
        y = _rms_norm(x, layer["norm2"]["scale"])
        x = x + _moe_or_mlp(layer, config, y)
    return x, pool_k, pool_v, None, None


def _latent_layers(params, config: TransformerConfig, pool_k, pool_v,
                   tables, positions, blk, off, x, live):
    """The latent blocks' layers (double layers with a shortcut, or
    single layers whose feed-forward is dense or routed:
    ``transformer.latent_layers`` puts either together), same contract
    as :func:`_dense_layers`.  Every attention sub-layer has a pool
    layer of its own; its row is the latent ``c_kv`` (``pool_k``) and
    the one rotary key (``pool_v``), written through the same function
    as a K and a V.  Every step attends in the absorbed
    form, over the latent rows themselves: a decode step could not
    expand a view (32 lanes x 8192 rows x 64 heads), and a 512-row
    prefill chunk against an 8192-row view measured 8.75 ms absorbed,
    11.26 ms expanded on a v5e (PERF.md, PR 27).  One query row a lane
    (the decode step) goes through the paged latent kernel where
    :func:`attend_path` says it can run: each lane reads its own latent
    pages and rotary keys up to its own position, an idle lane none
    (1.28 ms of gathers and scores a sub-layer -> under 0.23 ms alone
    on a v5e at 22 live lanes of 300-6100 rows: PERF.md, PR 33).  Wider
    queries (the prefill chunk) and every step off the TPU attend a key
    block of the view at a time, through ``_layer_views`` of that part
    of the table, only as far as the lanes reach, not the whole
    ``max_request_len`` view at once: a decode span of 4 steps over 32
    lanes of 600-3000 rows 66.3 against 125.0 ms, a mixed dispatch 117.2
    against 237.9 (PR 27).
    A fused step's ``tables`` are its row groups (:func:`_attend_rows`).
    The rows ``live`` [B, C] says are dead choose no expert.  Also
    returns the step's routing counts int32[7]: the expert layers'
    (ops/moe.py ROUTING_COUNTS) summed, then the rows that chose."""
    block_size = pool_k.shape[3]
    rope = config.qk_rope_head_dim

    def attend(sub, attn, y):
        nonlocal pool_k, pool_v
        q_nope, q_rope, c_kv, k_rope = latent_qkv(
            attn, y, positions, config)
        pool_k, pool_v = _write_rows(
            pool_k, pool_v, sub, blk, off,
            c_kv[:, :, None, :], k_rope[:, :, None, :])

        def context(tables, positions, q_abs, q_rope):
            if attend_path(config.block, positions.shape[1], tables.shape[1],
                           pool_k, pool_v, config.head_dim) == "kernel":
                return paged_latent_decode_attention(
                    q_abs[:, :, 0], q_rope[:, :, 0], pool_k, pool_v, sub,
                    tables, positions[:, 0], scale=latent_scale(config),
                    interpret=_kernel_mode() == "interpret")[:, :, None]
            entries = key_block_entries(tables.shape[1], block_size)

            def view_block(i):
                part = jax.lax.dynamic_slice_in_dim(
                    tables, i * entries, entries, axis=1)
                view_c, view_r = _layer_views(pool_k, pool_v, sub, part,
                                              rope)
                return view_c[:, 0], view_r[:, 0]

            return latent_context_blocks(
                q_abs, q_rope, view_block, entries * block_size, positions,
                config).astype(config.dtype)

        return latent_absorbed(
            attn, q_nope,
            lambda q_abs: _attend_rows(tables, positions, context, q_abs,
                                       q_rope),
            config)

    x, counts = latent_layers(params, x, config, attend, live)
    counts = jnp.concatenate([counts, jnp.sum(live, dtype=jnp.int32)[None]])
    return x, pool_k, pool_v, counts, None


def _paired_queries(q, paired: int, kv_heads: int):
    """``q`` [B, H, C, hd] laid out for a pool whose rows hold ``paired`` KV
    heads side by side (``kv_blocks.KVRowLayout.heads_paired``): each query
    head in its KV head's part of a row of ``paired x hd`` zeros, [B, H, C,
    paired x hd].  KV head j lies in part ``j % paired`` of array row ``j //
    paired`` and serves ``H / kv_heads`` consecutive query heads; their
    products with the row's other heads' values are exact zeros, so the
    scores are the head's own, and the query heads of one array row are one
    query group of ``paired x H / kv_heads`` heads."""
    b, h, c, hd = q.shape
    q = q.reshape(b, kv_heads // paired, paired, h // kv_heads, c, 1, hd)
    mine = jnp.eye(paired, dtype=bool).reshape(1, 1, paired, 1, 1, paired, 1)
    return jnp.where(mine, q, 0).reshape(b, h, c, paired * hd)


def _paired_context(o, paired: int, kv_heads: int):
    """A context [B, H, C, paired x hd] over paired value rows -> each
    query head's own part of it, [B, H, C, hd]."""
    b, h, c, wide = o.shape
    o = o.reshape(b, kv_heads // paired, paired, h // kv_heads, c, paired,
                  wide // paired)
    return jnp.stack([o[:, :, j, :, :, j] for j in range(paired)],
                     axis=2).reshape(b, h, c, wide // paired)


def _tables_by_kind(tables, positions, blk, kinds: int, page_rows: int):
    """A step's tables and write targets, a kind of a pool by layer kind
    at a time -> (tables of each kind, ``blk`` of each kind).  A lane's
    table holds its kinds' tables side by side (``[B, kinds x T]``, the full
    kind first: ``kv_blocks``), so every program takes one table a lane
    whatever the pool, and the steps' own ``blk`` — looked up in the first
    ``T`` entries — is the full kind's.  A further kind's is looked up here
    in its own entries, at the same ``positions``, and lands in that kind's
    scratch block wherever the step sent the full kind's there (an idle
    lane, a dead row).  ``tables`` may be a fused step's row groups
    (:func:`_attend_rows`).  One kind: what came in."""
    if kinds == 1:
        return [tables], [blk]
    fused = isinstance(tables, tuple)
    groups = tables if fused else (RowGroup(tables, positions),)
    width = groups[0].tables.shape[1] // kinds
    out_tables, out_blk = [], []
    for kind in range(kinds):
        parts = tuple(
            g._replace(tables=g.tables[:, kind * width:(kind + 1) * width])
            for g in groups)
        out_tables.append(parts if fused else parts[0].tables)
        if kind == 0:
            out_blk.append(blk)
            continue
        own = [jnp.take_along_axis(g.tables, g.positions // page_rows,
                                   axis=1) for g in parts]
        own = (jnp.concatenate([o.reshape(-1) for o in own])[None] if fused
               else own[0])
        out_blk.append(jnp.where(blk != 0, own, 0))
    return out_tables, out_blk


def _gqa_moe_layers(params, config: TransformerConfig, pool_k, pool_v,
                    tables, positions, blk, off, x, live, carried=None):
    """The 'gqa_moe' block's layers (``transformer.gqa_moe_layers`` puts
    a layer together), same contract as :func:`_dense_layers` and the
    same cache plumbing: a layer's K and V rows are written through
    :func:`_write_rows` first and its queries then attend the lane's
    view through :func:`_attend_view`.  What a query sees is
    ``transformer.attend_reach`` of its position: itself and everything
    before it, or — under generation by diffusion over blocks — its
    whole aligned block, the rows after it too, which the step has just
    written (write-then-attend is what makes a block's rows see one
    another).  A diffusion pass's ``diffusion_block`` rows a lane are one
    aligned block with one reach and attend as one query group through
    the paged kernel where :func:`attend_path` says it can run, as one
    row a lane does under the causal mask; the prefill chunk, rows of
    many blocks, runs the key-block loop.  Also returns the step's
    routing counts int32[7], as :func:`_latent_layers` does.  A fused
    step's ``tables`` are its row groups (:func:`_attend_rows`).

    Where the model's layers name their operator
    (``TransformerConfig.layer_operators``) the pool holds the attention
    layers' rows alone, two 64-wide KV heads side by side in a row
    (``kv_blocks.KVRowLayout`` ``heads_paired``: the queries go in laid
    into their head's part of a row of zeros and the context comes back
    out of it, so every path above sees heads of 128), and ``carried`` =
    (:class:`Recurrent`, _, ``slots`` [B] or None, _) holds the short
    convolutions' states, an array a convolution layer BY SLOT (None:
    lane b IS slot b, the decode lanes).  A convolution reads its lane's
    state — zeros where the lane's first row is row 0, whatever the slot
    holds — and leaves ``B * u`` of the lane's last ``conv_taps - 1`` LIVE
    rows (``ops/short_conv.py``); a lane with no live row (an idle lane, a
    slot between two chunks of its prompt) keeps what it held.  The new
    states come back as the fifth result.  In a fused step the projections
    and the gates run once over all the rows and only the filter over the
    state runs a group of lanes at a time (:func:`_lane_groups`), so the
    experts behind it see ONE grouping of every live row.

    Where the model caches BY LAYER KIND (a layer names "window":
    ``kv_blocks.PagedKVPool``) ``pool_k`` and ``pool_v`` are an array a
    kind and a lane's table its kinds' tables side by side
    (:func:`_tables_by_kind`): a layer writes and attends its OWN kind's
    arrays through its own kind's table — the "window" kind under
    ``attention_window`` (the paged kernel walks from the window's first
    page, the key-block loop from its first block), the full kind as
    ever — and a "global" layer rotates nothing."""
    # a fused step's groups bring their own positions, which ARE their
    # reach: generation by diffusion has a mixed entry point of its own
    reach = attend_reach(config, positions)
    dtype = config.dtype
    h_kv = config.kv_heads
    by_kind = isinstance(pool_k, tuple)
    pools = list(zip(pool_k, pool_v)) if by_kind else [(pool_k, pool_v)]
    kind_tables, kind_blk = _tables_by_kind(tables, positions, blk,
                                            len(pools), _page_rows(pool_k))
    paired = h_kv // pools[0][0].shape[2]
    scale = config.head_dim ** -0.5

    def attend(row, attn, y, operator):
        # the layer's kind: its pool and table, its window, its rotation
        kind = int(operator == "window")
        window = config.attention_window if kind else None
        pk, pv = pools[kind]
        q, k, v = gqa_qkv(attn, y, positions, config, operator != "global")
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        if paired > 1:
            with jax.named_scope("attention"):
                q = _paired_queries(q, paired, h_kv)
                k = k.reshape(*k.shape[:2], *pk.shape[2::2])
                v = v.reshape(*v.shape[:2], *pv.shape[2::2])
        pk, pv = pools[kind] = _write_rows(pk, pv, row, kind_blk[kind], off,
                                           k, v)

        def view(tables, reach, q):
            return _attend_view(q, pk, pv, row, tables, reach, window,
                                config.diffusion_block, scale)

        with jax.named_scope("attention"):
            o = _attend_rows(kind_tables[kind], reach, view, q)
            return o if paired == 1 else _paired_context(o, paired, h_kv)

    # no convolution, nothing carried: `conv` is never called
    recurrent, groups = _lane_groups(
        tables, positions, live,
        carried or (Recurrent(None, ()), None, None, 0))
    states = list(recurrent.states)

    def conv(idx, weights, y):
        # the projections once over every row, the filter a group of lanes
        # at a time: each reads its slots' states as the array now stands
        # and leaves the new ones there, so the decode lanes' idle lane that
        # IS the filling slot keeps what the chunk's group has just left
        gate_c, g = conv_gates(weights, y, dtype)

        def filtered(group, g):  # the group's lanes' rows, [B, 1, C, d]
            slots = group.slots
            rows_done = jnp.sum(group.live, axis=1,
                                dtype=jnp.int32)  # live rows lead
            fresh = group.live[:, 0] & (group.positions[:, 0] == 0)
            with jax.named_scope("conv_state"):
                state = states[idx] if slots is None else states[idx][slots]
                state = jnp.where(fresh[:, None, None], 0, state)
            c, window = conv_filter(weights, g[:, 0], state, dtype)
            new = state_after(window, rows_done, config.conv_taps)
            with jax.named_scope("conv_state"):
                states[idx] = (new if slots is None
                               else states[idx].at[slots].set(new))
            return c[:, None]

        g = g[:, None]  # as :func:`_by_group` cuts rows: one head of them
        c = (_by_group(groups, filtered, g) if isinstance(tables, tuple)
             else filtered(groups[0], g))
        return conv_out(weights, gate_c, c[:, 0], dtype)

    x, counts = gqa_moe_layers(params, x, config, attend, live, conv)
    counts = jnp.concatenate([counts, jnp.sum(live, dtype=jnp.int32)[None]])
    pool_k, pool_v = zip(*pools) if by_kind else pools[0]
    return (x, pool_k, pool_v, counts,
            Recurrent(None, tuple(states)) if states else None)


class Recurrent(NamedTuple):
    """What the step programs of a model with a state BY SLOT carry beside
    K and V, all donated, through every program and back to the engine.  A
    'retention' block: ``gate`` the pool's third array (float32 ``[layers,
    kv_heads, num_blocks x block_size]``, each unfolded row's log gate in
    column ``page x block_size + offset``) and ``states``, an array a layer
    ``[slots, kv_heads, state_rows, phi_width]``: the lanes' recurrent
    states (``kv_blocks.init_retention_states``).  A model whose layers
    name the short convolution: ``gate`` None and ``states`` an array a
    convolution layer ``[slots, conv_taps - 1, d_model]``
    (``kv_blocks.init_conv_states``).  A step's rows cross the states a
    group of lanes at a time (:func:`_lane_groups`): one group in a chunk or
    a decode step, the chunk's lane and the decode lanes in a mixed
    dispatch's fused first step (:func:`_mixed_first_step`)."""

    gate: Optional[jax.Array]
    states: Tuple[jax.Array, ...]


def tail_pages(rows: int, block_size: int) -> int:
    """Pages that cover a lane's unfolded rows through a step that adds
    ``rows``: a tail is under one key block going in (a lane folds as soon
    as its tail holds one) and a fold starts on a page's first row."""
    if KEY_BLOCK % block_size:
        raise ValueError(
            f"a fold covers whole pages: block_size {block_size} must "
            f"divide KEY_BLOCK {KEY_BLOCK}")
    return -(-(KEY_BLOCK + rows) // block_size)


class _Tail(NamedTuple):
    """What every 'retention' layer of a step reads of one group of lanes
    (:func:`_retention_layers`): ``window`` [B, Wp] the pages from each
    lane's fold point on, ``columns`` [B, W] their rows as columns of the
    gate array, ``q_row`` [B, C] each query's row of the window (a dead
    query: -1), ``has_state`` [B] the lanes that have folded anything, and
    the group's ``positions`` and ``slots``."""

    positions: jax.Array
    slots: Optional[jax.Array]
    window: jax.Array
    columns: jax.Array
    q_row: jax.Array
    has_state: jax.Array


def _retention_layers(params, config: TransformerConfig, pool_k, pool_v,
                      tables, positions, blk, off, x, live, carried):
    """The 'retention' block's layers (``transformer.retention_layers`` puts
    a layer together), same contract as :func:`_dense_layers` plus
    ``carried`` = (:class:`Recurrent`, ``folded`` [B], ``slots`` [B] or
    None, ``grow``): lane b has folded its rows before ``folded[b]`` (a
    multiple of ``KEY_BLOCK``) into the state of slot ``slots[b]`` (None:
    lane b IS slot b, the decode lanes) and holds the rows from there on in
    its pages; ``grow`` bounds the rows a tail gains before its next fold
    (the chunk's width, the span's steps).  A fused step's ``tables`` are
    its row groups, which bring their own (:func:`_lane_groups`).

    A layer writes its rows' K, V (:func:`_write_rows`) and log gate — once
    over all the step's rows — then reads, a group of lanes at a time, ONE
    window of pages a lane, from the fold point on — at most a key block
    and ``grow`` rows, whatever the request's length: the gate's
    running log is the window's running sum, the unfolded rows weigh in by
    their squared scores (``ops.retention.tail_sums``, a running sum, no
    softmax), everything before them by ``phi(q)`` against the state
    (``state_sums``), which a group whose lanes have folded nothing
    never reads.  Nothing here writes a state: a fold is the step
    program's last phase (:func:`fold_lanes`).  Returns no routing counts
    and, fifth, the :class:`Recurrent` with the updated gate array."""
    recurrent, groups = _lane_groups(tables, positions, live, carried)
    gate, states = recurrent
    bs = pool_k.shape[3]
    dtype = config.dtype

    def tail(group):
        pages = tail_pages(group.grow, bs)
        folded = group.folded
        first = (folded // bs)[:, None] + jnp.arange(pages)[None, :]
        window = jnp.take_along_axis(
            group.tables, jnp.minimum(first, group.tables.shape[1] - 1),
            axis=1)  # [B, Wp]
        # the window's rows as columns of the gate array, [B, W]
        columns = (window[:, :, None] * bs
                   + jnp.arange(bs)[None, None, :]).reshape(
                       window.shape[0], -1)
        q_row = jnp.where(group.live, group.positions - folded[:, None],
                          -1)  # [B, C]
        has_state = (folded > 0) & jnp.any(group.live, axis=1)
        return _Tail(group.positions, group.slots, window, columns, q_row,
                     has_state)

    tails = [tail(group) for group in groups]

    def attend(layer_idx, attn, y):
        nonlocal pool_k, pool_v, gate
        q, k, v = retention_qkv(attn, y, positions, config)
        a = retention_gate(attn, y)  # [B, C, h_kv]
        pool_k, pool_v = _write_rows(
            pool_k, pool_v, layer_idx, blk, off,
            k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
        with jax.named_scope("kv_write"):
            gate = gate.at[layer_idx, :, blk * bs + off].set(a)

        def sums(tail, q):  # of one group's lanes
            with jax.named_scope("retention_tail"):
                k_win, v_win = _layer_views(pool_k, pool_v, layer_idx,
                                            tail.window)
                cum_win = jnp.cumsum(gate[layer_idx, :, tail.columns],
                                     axis=1)
                cum_q = jnp.take_along_axis(
                    cum_win, jnp.maximum(tail.q_row, 0)[:, :, None], axis=1)
            unfolded = tail_sums(q, k_win, v_win, cum_q, cum_win,
                                 tail.q_row, dtype)

            def from_state():
                with jax.named_scope("retention_state"):
                    lane_states = (states[layer_idx] if tail.slots is None
                                   else states[layer_idx][tail.slots])
                return state_sums(q, lane_states, cum_q, tail.has_state)

            state = jax.lax.cond(
                jnp.any(tail.has_state), from_state,
                lambda: (jnp.zeros_like(unfolded[0]),
                         jnp.zeros_like(unfolded[1])))
            return retention_output(unfolded, state, dtype)

        if isinstance(tables, tuple):
            return _by_group(tails, sums, q)
        return sums(tails[0], q)

    x = retention_layers(params, x, config, attend)
    return x, pool_k, pool_v, None, Recurrent(gate, states)


@jax.named_scope("retention_fold")
def fold_lanes(pool_k, pool_v, recurrent: Recurrent, tables, folded,
               lengths, live, slots=None) -> Recurrent:
    """A step program's last phase: every lane whose tail now holds a key
    block — ``lengths[b] - folded[b] >= KEY_BLOCK``, ``lengths`` the rows
    it holds once the step is done — folds rows ``folded[b] ..
    folded[b] + KEY_BLOCK - 1`` into its slot's state, every layer
    (``ops.retention.fold_update``), from the pages the rows lie in.  A
    loop over the lanes that are due, one at a time (none, most
    dispatches); the engine's ``.consume`` does the same arithmetic and
    hands the pages behind the fold back.  The states change nowhere
    else."""
    gate, states = recurrent
    _, _, h_kv, bs, hd = pool_k.shape
    entries = KEY_BLOCK // bs
    due = live & (lengths - folded >= KEY_BLOCK)
    order = jnp.argsort(~due)  # the due lanes first (a stable sort)

    def fold_lane(i, states):
        lane = order[i]
        slot = lane if slots is None else slots[lane]
        start = folded[lane]
        pages = jax.lax.dynamic_slice_in_dim(
            tables[lane], start // bs, entries)
        columns = (pages[:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
        rows = lambda pool, layer: pool[layer, pages].transpose(
            1, 0, 2, 3).reshape(h_kv, KEY_BLOCK, hd)
        return tuple(
            state.at[slot].set(fold_update(
                state[slot], rows(pool_k, layer), rows(pool_v, layer),
                gate[layer, :, columns], start > 0))
            for layer, state in enumerate(states))

    states = jax.lax.fori_loop(0, jnp.sum(due, dtype=jnp.int32), fold_lane,
                               states)
    return Recurrent(gate, states)


# the layers' counts, then the rows that chose: what a routed step returns
N_STEP_COUNTS = len(ROUTING_COUNTS) + 1

_LAYERS = {"dense": _dense_layers, "gqa_moe": _gqa_moe_layers,
           "latent_shortcut": _latent_layers, "latent_moe": _latent_layers,
           "retention": _retention_layers}


def _run_layers(params, config: TransformerConfig, *args, carried=None):
    """The block's layer loop: the one place a step program's layers
    run, whatever the step (prefill chunk, decode step, verify chunk).
    Returns (x, pool_k, pool_v, a routed block's routing counts or None,
    and the :class:`Recurrent` a model with a state by slot hands on —
    a 'retention' block's gate array and states, the short convolutions'
    new states — or None).  ``carried`` = (:class:`Recurrent`, ``folded``,
    ``slots``, ``grow``) is such a model's alone."""
    if carried is None:
        return _LAYERS[config.block](params, config, *args)
    return _LAYERS[config.block](params, config, *args, carried)


def _step_outputs(routing: bool, counts, recurrent, *outputs):
    """A step's outputs: then a routed block's routing counts when the
    caller asked for them, then — last — the :class:`Recurrent` of a
    model that carries one."""
    if routing:
        outputs += (counts,)
    return outputs if recurrent is None else outputs + (recurrent,)


def _settled(config: TransformerConfig, pool_k, pool_v, recurrent, *fold):
    """The :class:`Recurrent` once a step program's rows are in: a
    'retention' block folds the lanes that are due (:func:`fold_lanes`),
    its last phase; the short convolutions' states are already what the
    rows left."""
    if config.block == "retention":
        return fold_lanes(pool_k, pool_v, recurrent, *fold)
    return recurrent


def _prefill_rows(params, config: TransformerConfig, pool_k, pool_v, tables,
                  starts, active, tokens, last_rows, carried=None):
    """A prefill chunk's rows through the layers (see
    :func:`paged_prefill_step`): the final hidden states [P, C, d], the
    pool and the routing counts, before any head."""
    dtype = config.dtype
    chunk = tokens.shape[1]
    bs = _page_rows(pool_k)
    positions = starts[:, None] + jnp.arange(chunk)[None, :]  # [P, C]
    blk = jnp.take_along_axis(tables, positions // bs, axis=1)  # [P, C]
    blk = jnp.where(active[:, None], blk, 0)
    off = positions % bs
    x = params["embed"][tokens].astype(dtype)  # [P, C, d]
    if config.positional != "rope":
        x = x + params["pos_embed"][positions].astype(dtype)
    # a chunk's rows after its last real one are padding
    live = active[:, None] & (
        jnp.arange(chunk)[None, :] <= last_rows[:, None])
    if carried is not None:
        # a chunk of a model with a state pads FORWARD, past the prompt's
        # last row: the padding's rows land in the scratch block, not in
        # a page
        blk = jnp.where(live, blk, 0)
    return _run_layers(
        params, config, pool_k, pool_v, tables, positions, blk, off, x,
        live, carried=carried)


def paged_prefill_step(
    params,
    config: TransformerConfig,
    pool_k,
    pool_v,
    tables,
    starts,
    active,
    tokens,
    last_rows,
    routing: bool = False,
    recurrent: Optional[Recurrent] = None,
    folded=None,
    slots=None,
) -> Tuple[jax.Array, ...]:
    """One width-C prefill chunk for P slot lanes at once.

    ``tokens`` [P, C] are each lane's chunk at virtual positions
    ``starts[p] .. starts[p]+C-1`` against its own ``tables[p]``;
    ``last_rows`` [P] select each lane's logits row (its prompt's final
    real token, when this chunk is its last — with a bucket-padded tail
    that is not the chunk's last row).  Returns
    (logits [P, vocab], pool_k, pool_v).  The chunk's K/V land in the
    blocks first, then its queries attend the lane's whole gathered
    view under the per-query causal band — intra-chunk causality falls
    out of the same mask that orders chunk vs history, exactly like the
    dense ``_decode_chunk``.  Only the selected rows' lm_head projection
    is computed (a full [P, C, vocab] f32 buffer would dominate the
    step at real vocab sizes).

    A model with a state by slot also takes ``recurrent``
    (:class:`Recurrent`), each lane's slot ``slots`` [P] and (a 'retention'
    block's) fold point ``folded`` [P], and returns the :class:`Recurrent`
    last, after a routed block's counts: a 'retention' chunk reads the
    lane's state and its unfolded rows, and where it completes a key
    block, the program's last phase folds it (:func:`fold_lanes`); a
    convolution reads the two rows its slot holds (zeros at row 0) and
    leaves those of the chunk's last live rows.  Such a chunk pads
    FORWARD, past the prompt's last row.

    Inactive lanes write to the scratch block and compute garbage the
    caller ignores.  NOTE: the engine deliberately dispatches P=1 (one
    lane per chunk) — a static multi-lane shape bills every dispatch
    for its padded lanes (~2x worse in an earlier round's CPU timing, not
    measured on the chip); see engine._run_prefill_chunk before batching
    lanes here.
    """
    dtype = config.dtype
    carried = (None if recurrent is None
               else (recurrent, folded, slots, tokens.shape[1]))
    x, pool_k, pool_v, counts, recurrent = _prefill_rows(
        params, config, pool_k, pool_v, tables, starts, active, tokens,
        last_rows, carried)

    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["final_norm"]["scale"], config.norm_eps)
        head_in = jnp.take_along_axis(
            x, last_rows[:, None, None], axis=1)  # [P,1,d]
        logits = (head_in
                  @ params["lm_head"].astype(dtype)).astype(jnp.float32)
    if recurrent is not None:
        recurrent = _settled(config, pool_k, pool_v, recurrent, tables,
                             folded, starts + last_rows + 1, active, slots)
    return _step_outputs(routing, counts, recurrent, logits[:, 0], pool_k,
                         pool_v)


def paged_decode_step(
    params,
    config: TransformerConfig,
    pool_k,
    pool_v,
    block_tables,
    lengths,
    active,
    tokens,
    routing: bool = False,
    recurrent: Optional[Recurrent] = None,
    folded=None,
    grow: int = 1,
) -> Tuple[jax.Array, ...]:
    """One decode token for every slot in the pool at once.

    ``tokens`` [S] (this step's input token per slot, 0 for inactive
    slots), ``lengths`` [S] (each slot's cache fill = this write's
    position), ``block_tables`` [S, T], ``active`` [S] bool.  Returns
    (logits [S, vocab], pool_k, pool_v); inactive rows compute garbage
    the caller ignores — their K/V writes are routed to the scratch
    block so the pool's live data is never touched.

    A model with a state by slot also takes ``recurrent`` (lane s is
    slot s) and returns the :class:`Recurrent` last: a 'retention' block's
    (with the lanes' fold points ``folded`` [S]) with the step's log gates
    written — it folds nothing: that is the span's last phase, and ``grow``
    says how many steps a tail may gain before it; the short convolutions'
    states shifted by the step's row, an idle lane's as they were.
    """
    dtype = config.dtype
    bs = _page_rows(pool_k)
    positions = lengths  # [S]
    # each slot's write target; inactive lanes land in scratch block 0
    blk = jnp.take_along_axis(
        block_tables, (positions // bs)[:, None], axis=1)[:, 0]
    blk = jnp.where(active, blk, 0)
    off = positions % bs
    x = params["embed"][tokens].astype(dtype)[:, None, :]  # [S, 1, d]
    if config.positional != "rope":
        x = x + params["pos_embed"][positions].astype(dtype)[:, None, :]
    # every slot a one-row chunk at its own position: the same layer loop
    carried = (None if recurrent is None
               else (recurrent, folded, None, grow))
    x, pool_k, pool_v, counts, recurrent = _run_layers(
        params, config, pool_k, pool_v, block_tables, positions[:, None],
        blk[:, None], off[:, None], x, active[:, None], carried=carried)

    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["final_norm"]["scale"], config.norm_eps)
        logits = (x @ params["lm_head"].astype(dtype)).astype(jnp.float32)
    return _step_outputs(routing, counts, recurrent, logits[:, 0], pool_k,
                         pool_v)


def paged_decode_span(
    params,
    config: TransformerConfig,
    pick_fn,
    span: int,
    eos,
    pool_k,
    pool_v,
    tables,
    lengths,
    active,
    tokens,
    temps,
    keys,
    budgets,
    routing: bool = False,
    recurrent: Optional[Recurrent] = None,
    folded=None,
    decode_step=None,
) -> Tuple[jax.Array, ...]:
    """Advance every active lane up to ``span`` tokens in ONE dispatch.

    The scan body is EXACTLY :func:`paged_decode_step` plus the
    engine's ``pick_fn(logits, temps, keys[:, i])`` token policy, so
    the emitted math is span-invariant; a lane whose request finishes
    mid-span (budget spent, or EOS sampled) deactivates itself — its
    remaining iterations write to the scratch block and its surplus
    emissions are ignored host-side.  Returns
    (emitted [span, S], pool_k, pool_v), and with ``routing`` the
    routing counts summed over the span's steps last.
    ``pick_fn``/``span``/``eos`` are trace-time constants (the engine
    closes over them under jit).

    A model with a state by slot carries its :class:`Recurrent` through
    the scan with the pool (one buffer each, updated in place: the compiler
    keeps no second copy of a 'retention' block's 5 GB of states) and
    returns it last.  A 'retention' block's steps (``folded`` [S]) write
    their log gates and only READ the states; a lane whose tail holds a key
    block once the span is done folds it in the program's last phase
    (:func:`fold_lanes`).  The short convolutions' states shift a row a
    step.

    ``decode_step`` stands in for :func:`paged_decode_step` in the scan's
    body (the same signature): an engine hands every span it builds ONE
    ``jax.jit(paged_decode_step, inline=True)``, so the step over the lanes
    — the same shapes in the decode program and in every mixed program — is
    traced once for all of them and replayed into each (nothing of it is
    left in the lowered text: the program is what it was).
    """
    emitted, pk, pv, recurrent, lens, counts = _span_steps(
        params, config, pick_fn, span, eos, pool_k, pool_v, tables, lengths,
        active, tokens, temps, keys, budgets, routing, recurrent, folded,
        grow=span, decode_step=decode_step)
    if recurrent is not None:
        recurrent = _settled(config, pk, pv, recurrent, tables, folded,
                             lens, active)
    return _step_outputs(routing, counts, recurrent, emitted, pk, pv)


def _span_steps(params, config: TransformerConfig, pick_fn, steps: int, eos,
                pool_k, pool_v, tables, lengths, active, tokens, temps, keys,
                budgets, routing: bool, recurrent, folded, grow: int,
                decode_step=None):
    """The scan of :func:`paged_decode_span` — ``steps`` step-identical
    :func:`paged_decode_step` iterations with the pick between them — before
    the program's last phase: a whole span's, or the steps a mixed dispatch's
    fused first step leaves (:func:`paged_mixed_step`; ``grow`` is the rows a
    'retention' tail gains over the WHOLE dispatch, whatever share of it
    this scan is).  Returns (emitted [steps, S], pool_k, pool_v, the
    :class:`Recurrent` or None, the lanes' lengths after the last step, the
    routing counts summed over the steps or None)."""
    step = decode_step or paged_decode_step

    def body(carry, i):
        pk, pv, rec, lens, toks, alive, *counts = carry
        logits, pk, pv, *rest = step(
            params, config, pk, pv, tables, lens, alive, toks,
            routing=routing, recurrent=rec, folded=folded, grow=grow)
        if rec is not None:
            rec = rest.pop()
        with jax.named_scope("sample"):
            nxt = pick_fn(logits, temps, keys[:, i])
        lens = lens + alive.astype(jnp.int32)
        cont = alive & (i + 1 < budgets)
        if eos is not None:
            cont = cont & (nxt != eos)
        counts = [c + s for c, s in zip(counts, rest)]
        return (pk, pv, rec, lens, nxt, cont, *counts), nxt

    carry = (pool_k, pool_v, recurrent, lengths, tokens, active,
             *([jnp.zeros((N_STEP_COUNTS,), jnp.int32)] if routing else []))
    (pk, pv, recurrent, lens, _, _, *counts), emitted = jax.lax.scan(
        body, carry, jnp.arange(steps))
    return emitted, pk, pv, recurrent, lens, counts[0] if counts else None


def _decode_loop_impl(
    step_fn,
    pick_fn,
    span: int,
    k_units: int,
    eos,
    pool_k,
    pool_v,
    tables,
    lengths,
    active,
    tokens,
    temps,
    keys,
    budgets,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The device-resident multi-step loop's shared body, parameterized
    by the single-token decode step (``paged_decode_step`` here, the
    shard_map-local twin in serving/sharded.py) so both engines run the
    IDENTICAL loop construction.

    Each while-loop iteration is one SPAN-UNIT: the exact scan body of
    :func:`paged_decode_span`, with the emission index flattened across
    units (unit u, step j consumes ``keys[:, u*span + j]`` and checks
    ``u*span + j + 1 < budgets`` — arithmetically identical to the
    re-marshaled per-dispatch budget a K=1 engine would compute, since a
    still-alive lane accepted exactly ``span`` tokens per earlier unit).
    The unit's emissions land in the on-device ring at rows
    ``[u*span, (u+1)*span)``.

    Early exit — the "lanes changed" device flag: the loop continues
    only while every initially-active lane is still alive.  The moment
    any lane deactivates (budget spent or EOS), the host's next plan
    would differ (retire, admit, preempt), so the loop stops at that
    span boundary and hands control back.  Whenever no lane changed,
    the K=1 host would have re-issued the IDENTICAL decode plan — the
    loop is literally consecutive identical decode plans batched into
    one launch, which is the whole bit-exactness argument.

    Returns (ring [k_units*span, S], units ran [], pool_k, pool_v);
    ring rows at and past ``units*span`` are zeros the host never
    reads.  An all-inactive call (warmup) runs zero units.
    """
    s = tables.shape[0]

    def unit_body(carry, j):
        u, pk, pv, lens, toks, alive = carry
        logits, pk, pv = step_fn(pk, pv, tables, lens, alive, toks)
        i = u * span + j
        with jax.named_scope("sample"):
            nxt = pick_fn(logits, temps, jnp.take(keys, i, axis=1))
        lens = lens + alive.astype(jnp.int32)
        cont = alive & (i + 1 < budgets)
        if eos is not None:
            cont = cont & (nxt != eos)
        return (u, pk, pv, lens, nxt, cont), nxt

    def cond(carry):
        u, ring, pk, pv, lens, toks, alive = carry
        # continue while units remain AND the lane set is unchanged —
        # jnp.any(alive) also exits an all-inactive (warmup) call at
        # unit 0 instead of spinning K units of scratch-block work
        return ((u < k_units) & jnp.any(alive)
                & ~jnp.any(active & ~alive))

    def body(carry):
        u, ring, pk, pv, lens, toks, alive = carry
        (_, pk, pv, lens, toks, alive), emitted = jax.lax.scan(
            unit_body, (u, pk, pv, lens, toks, alive), jnp.arange(span))
        ring = jax.lax.dynamic_update_slice(ring, emitted, (u * span, 0))
        return (u + 1, ring, pk, pv, lens, toks, alive)

    ring = jnp.zeros((k_units * span, s), jnp.int32)
    units, ring, pk, pv, _, _, _ = jax.lax.while_loop(
        cond, body,
        (jnp.asarray(0, jnp.int32), ring, pool_k, pool_v, lengths,
         tokens, active))
    return ring, units, pk, pv


def paged_decode_loop(
    params,
    config: TransformerConfig,
    pick_fn,
    span: int,
    k_units: int,
    eos,
    pool_k,
    pool_v,
    tables,
    lengths,
    active,
    tokens,
    temps,
    keys,
    budgets,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Up to ``k_units`` consecutive decode span-units in ONE dispatch —
    the device-resident step loop (``EngineConfig.steps_per_launch``).

    ``keys`` [S, k_units*span, 2] is the flat key window (the engine
    slices each lane's step-key schedule exactly as ``k_units``
    back-to-back span dispatches would); ``budgets`` [S] the remaining
    emission budgets at launch.  Returns (ring [k_units*span, S],
    units [], pool_k, pool_v) — see :func:`_decode_loop_impl` for the
    boundary semantics and the bit-exactness-with-K=1 argument.
    """

    def step_fn(pk, pv, tbl, lens, alive, toks):
        return paged_decode_step(
            params, config, pk, pv, tbl, lens, alive, toks)

    return _decode_loop_impl(
        step_fn, pick_fn, span, k_units, eos, pool_k, pool_v, tables,
        lengths, active, tokens, temps, keys, budgets)


def paged_verify_span(
    params,
    config: TransformerConfig,
    pick_fn,
    pool_k,
    pool_v,
    tables,
    lengths,
    active,
    tokens,
    widths,
    temps,
    keys,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Score every lane's drafted tokens in ONE width-W cached chunk —
    the speculative engine's draft-verify dispatch.

    ``tokens`` [S, W] carries, per lane, its last emitted token at
    column 0 followed by up to W-1 drafted tokens; ``widths`` [S] counts
    each lane's REAL columns (1 + its draft length — pad columns beyond
    that must hold -1 so they can never be accepted).  Column i sits at
    virtual position ``lengths[s] + i``; the chunk's K/V land in the
    lane's blocks first (pad and inactive-lane columns route to the
    scratch block), then every column's query attends the lane's whole
    gathered view under the per-query causal band — the identical
    write-then-attend math as :func:`paged_prefill_step`, just with the
    lm_head projected at EVERY column instead of one selected row.

    ``picked`` [S, W] is the token SEQUENTIAL decoding would emit at
    each position: column i's logits are picked with that emission's
    own temperature/PRNG key (``keys[:, i]`` — the engine slices the
    request's step-key schedule exactly as the decode span does), so
    the accepted prefix plus the correction pick reproduces the
    speculation-off stream bit for bit.  ``accepts`` [S] counts the
    leading drafted tokens the picks agree with
    (:func:`~kubeshare_tpu.models.decoding.speculative_acceptance` —
    the same rule as the dense draft-model decoder); the emitted round
    is ``picked[s, :accepts[s] + 1]``, host-truncated at budget/EOS.
    Columns past the accepted prefix leave stale K/V at positions the
    rewound host length masks out; the next dispatch overwrites them
    before any causal band can attend (the same write-then-attend order
    that makes CoW tails and pad rows dead).  Returns
    (picked [S, W], accepts [S], pool_k, pool_v).
    """
    dtype = config.dtype
    w = tokens.shape[1]
    bs = pool_k.shape[3]
    positions = lengths[:, None] + jnp.arange(w)[None, :]  # [S, W]
    valid = active[:, None] & (jnp.arange(w)[None, :] < widths[:, None])
    blk = jnp.take_along_axis(tables, positions // bs, axis=1)  # [S, W]
    blk = jnp.where(valid, blk, 0)
    off = positions % bs
    # pad columns hold -1 (an impossible token, so acceptance can never
    # match them); clamp the embed gather only — `tokens` itself keeps
    # the -1 sentinel for the acceptance comparison below
    x = params["embed"][jnp.maximum(tokens, 0)].astype(dtype)  # [S, W, d]
    if config.positional != "rope":
        x = x + params["pos_embed"][positions].astype(dtype)
    x, pool_k, pool_v, _, _ = _run_layers(
        params, config, pool_k, pool_v, tables, positions, blk, off, x,
        valid)

    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["final_norm"]["scale"], config.norm_eps)
        logits = (x @ params["lm_head"].astype(dtype)).astype(jnp.float32)
    # column i's pick is emission-number-identical to a width-1 decode
    # step at that position, so it consumes that emission's key
    with jax.named_scope("sample"):
        picked = jnp.stack(
            [pick_fn(logits[:, i], temps, keys[:, i]) for i in range(w)],
            axis=1)  # [S, W]
        accepts = speculative_acceptance(tokens[:, 1:], picked)
    return picked, accepts, pool_k, pool_v


def _spec_loop_impl(
    verify_fn,
    k_units: int,
    eos,
    max_order: int,
    redraft: float,
    width: int,
    pool_k,
    pool_v,
    tables,
    lengths,
    active,
    tokens,
    temps,
    keys,
    budgets,
    hist,
    hist_len,
    draft_caps,
    ring_tables,
    ring_lengths,
    ring_tokens,
    ring_temps,
    ring_keys,
    ring_budgets,
    ring_hist,
    ring_hist_len,
    ring_caps,
    ring_count,
):
    """Device residency v2's shared body — verify-in-loop plus the
    pending-lane admission ring — parameterized by the width-W verify
    dispatch (``paged_verify_span`` here, the shard_map-local twin in
    serving/sharded.py) so both engines run the IDENTICAL loop
    construction.

    Each while-loop iteration is one VERIFY-UNIT: draft on device
    (:func:`~kubeshare_tpu.serving.drafter.ngram_propose_rows` over the
    on-device right-aligned token-history window ``hist``), run the
    width-W verify dispatch, apply the exact acceptance rule, and
    advance every lane by its accepted prefix plus the correction pick
    — host-free.  Bit-exactness with the K=1 engine needs NO agreement
    between the device drafter and the host drafter: verification is
    exact-match against the engine's own pick policy, each column
    consuming the key of its emission number (``keys[s, done[s]+i]``
    where ``done`` counts the lane's in-loop emissions — a rejected
    column re-consumes the SAME key at the SAME emission number next
    unit, exactly as the host verify path re-slices the schedule), so
    draft content moves only the acceptance RATE.  Rejected columns'
    stale K/V rows sit at positions past the advanced length; the next
    unit's verify writes start exactly at the new length and cover the
    same width, overwriting them before any causal band attends — the
    identical write-then-attend argument the host verify path already
    relies on between rounds.

    Exit — at a unit boundary, the loop stops the moment host
    scheduling could differ: an occupied lane died (budget spent or
    EOS) and the ring had no pending lane to activate, the unit budget
    ``k_units`` ran out, the round's aggregate acceptance collapsed
    below the ``redraft`` threshold, or no lane could draft at all (the host
    falls back to the span loop rather than paying width-1 verify
    units).

    The admission ring: ``ring_*`` carry up to R pre-marshaled pending
    lanes (prompt blocks already prefilled, first token picked, PRNG
    schedules sliced) in admission order; ``ring_count`` is the number
    of real entries.  When an occupied lane dies at a unit boundary,
    the next ring entry is activated INTO that lane — in ascending lane
    order, so the host can replay activations deterministically — and
    the loop keeps going where v1 would exit, replan, and relaunch.
    Activation only ever targets a lane that was occupied at launch, so
    host-side free slots stay untouched.

    Returns (picked [K, S, W], accepted [K, S], drafted [K, S],
    units [], ring_used [], pool_k, pool_v).  Rows at and past
    ``units`` are zeros the host never reads; ``accepted`` is already
    clamped to ``drafted``.  The host replays emissions (budget/EOS
    truncation, retirement, ring activation) from these arrays alone —
    the arithmetic below is deliberately reproducible host-side.  An
    all-inactive call (warmup) runs zero units.
    """
    s = tables.shape[0]
    h = hist.shape[1]
    ring_size = ring_tables.shape[0]
    col = jnp.arange(width, dtype=jnp.int32)[None, :]

    def body(carry):
        (u, out_p, out_a, out_d, pk, pv, tbl, lens, alive, toks, tmp,
         kbuf, rem, done, hst, hlen, dcap, occ, head, _rd) = carry

        # -- draft: per-lane width is DATA (cap, budget), never a shape
        cap = jnp.clip(jnp.minimum(dcap, rem - 1), 0, width - 1)
        cap = jnp.where(alive, cap, 0)
        draft, n_draft = ngram_propose_rows(
            hst, hlen, cap, max_order, width - 1)

        # -- verify: column 0 is the lane's last emitted token, columns
        # 1..n_draft the proposal, -1 pad past that (never acceptable)
        ver = jnp.concatenate([toks[:, None], draft], axis=1)
        ver = jnp.where(alive[:, None], ver, -1)
        widths = 1 + n_draft
        kidx = jnp.clip(done[:, None] + col, 0, kbuf.shape[1] - 1)
        ukeys = jnp.take_along_axis(kbuf, kidx[:, :, None], axis=1)
        picked, accepts, pk, pv = verify_fn(
            pk, pv, tbl, lens, alive, ver, widths, tmp, ukeys)

        # -- emission arithmetic (the host replays exactly this)
        m = jnp.minimum(accepts, n_draft)
        emit = jnp.minimum(m + 1, rem)
        if eos is not None:
            is_eos = (picked == eos) & (col < emit[:, None])
            first_eos = jnp.min(jnp.where(is_eos, col, width), axis=1)
            emit = jnp.minimum(emit, first_eos + 1)
            eos_hit = first_eos < width
        else:
            eos_hit = jnp.zeros_like(alive)
        emit = jnp.where(alive, emit, 0)
        eos_hit = eos_hit & alive

        out_p = jax.lax.dynamic_update_slice(out_p, picked[None],
                                             (u, 0, 0))
        out_a = jax.lax.dynamic_update_slice(
            out_a, jnp.where(alive, m, 0)[None], (u, 0))
        out_d = jax.lax.dynamic_update_slice(
            out_d, jnp.where(alive, n_draft, 0)[None], (u, 0))

        # -- re-draft exit flag, judged on the lanes as they entered
        # the unit: the round's AGGREGATE acceptance collapsed (a
        # single cold lane must not end a K-unit launch for the whole
        # batch — its verify columns are wasted work bounded by W, and
        # its on-device history refreshes next unit anyway), or
        # nothing drafted at all (width-1 units are worse than the
        # span loop, so hand back)
        drafting = alive & (n_draft > 0)
        round_m = jnp.sum(jnp.where(drafting,
                                    m.astype(jnp.float32), 0.0))
        round_n = jnp.sum(jnp.where(drafting,
                                    n_draft.astype(jnp.float32), 0.0))
        rd = (round_m < redraft * round_n) | ~jnp.any(drafting)

        # -- advance lane state by the emitted prefix
        lens = lens + emit
        last = jnp.take_along_axis(
            picked, jnp.clip(emit - 1, 0, width - 1)[:, None],
            axis=1)[:, 0]
        toks = jnp.where(emit > 0, last, toks)
        rem = rem - emit
        done = done + emit
        cat = jnp.concatenate([hst, picked], axis=1)
        hidx = emit[:, None] + jnp.arange(h, dtype=jnp.int32)[None, :]
        hst = jnp.take_along_axis(cat, hidx, axis=1)
        hlen = jnp.minimum(hlen + emit, h)
        alive = alive & (rem > 0) & ~eos_hit

        # -- admission ring: activate pending lanes into retired ones,
        # ascending lane order (host replay depends on this order)
        if ring_size > 0:
            def admit(i, st):
                (tbl, lens, toks, tmp, kbuf, rem, done, hst, hlen,
                 dcap, alive, head) = st
                can = occ[i] & ~alive[i] & (head < ring_count)
                hsel = jnp.minimum(head, ring_size - 1)

                def sel(cur, new):
                    return jnp.where(can, new, cur)

                tbl = tbl.at[i].set(sel(tbl[i], ring_tables[hsel]))
                lens = lens.at[i].set(sel(lens[i], ring_lengths[hsel]))
                toks = toks.at[i].set(sel(toks[i], ring_tokens[hsel]))
                tmp = tmp.at[i].set(sel(tmp[i], ring_temps[hsel]))
                kbuf = kbuf.at[i].set(sel(kbuf[i], ring_keys[hsel]))
                rem = rem.at[i].set(sel(rem[i], ring_budgets[hsel]))
                done = done.at[i].set(jnp.where(can, 0, done[i]))
                hst = hst.at[i].set(sel(hst[i], ring_hist[hsel]))
                hlen = hlen.at[i].set(
                    sel(hlen[i], ring_hist_len[hsel]))
                dcap = dcap.at[i].set(sel(dcap[i], ring_caps[hsel]))
                alive = alive.at[i].set(alive[i] | can)
                head = head + can.astype(jnp.int32)
                return (tbl, lens, toks, tmp, kbuf, rem, done, hst,
                        hlen, dcap, alive, head)

            (tbl, lens, toks, tmp, kbuf, rem, done, hst, hlen, dcap,
             alive, head) = jax.lax.fori_loop(
                0, s, admit,
                (tbl, lens, toks, tmp, kbuf, rem, done, hst, hlen,
                 dcap, alive, head))

        return (u + 1, out_p, out_a, out_d, pk, pv, tbl, lens, alive,
                toks, tmp, kbuf, rem, done, hst, hlen, dcap, occ,
                head, rd)

    def cond(carry):
        (u, out_p, out_a, out_d, pk, pv, tbl, lens, alive, toks, tmp,
         kbuf, rem, done, hst, hlen, dcap, occ, head, rd) = carry
        # continue while units remain, no occupied lane sits dead
        # (ring exhausted or ring-less retire), acceptance holds, and
        # at least one lane is alive — jnp.any(alive) also exits an
        # all-inactive (warmup) call at unit 0
        return ((u < k_units) & jnp.any(alive)
                & ~jnp.any(occ & ~alive) & ~rd)

    out_p = jnp.zeros((k_units, s, width), jnp.int32)
    out_a = jnp.zeros((k_units, s), jnp.int32)
    out_d = jnp.zeros((k_units, s), jnp.int32)
    carry = (jnp.asarray(0, jnp.int32), out_p, out_a, out_d,
             pool_k, pool_v, tables, lengths, active, tokens, temps,
             keys, budgets, jnp.zeros((s,), jnp.int32), hist, hist_len,
             draft_caps, active, jnp.asarray(0, jnp.int32),
             jnp.asarray(False, bool))
    out = jax.lax.while_loop(cond, body, carry)
    (units, out_p, out_a, out_d, pk, pv, _, _, _, _, _, _, _, _, _,
     _, _, _, head, _) = out
    return out_p, out_a, out_d, units, head, pk, pv


def paged_spec_loop(
    params,
    config: TransformerConfig,
    pick_fn,
    k_units: int,
    eos,
    max_order: int,
    redraft: float,
    width: int,
    pool_k,
    pool_v,
    tables,
    lengths,
    active,
    tokens,
    temps,
    keys,
    budgets,
    hist,
    hist_len,
    draft_caps,
    ring_tables,
    ring_lengths,
    ring_tokens,
    ring_temps,
    ring_keys,
    ring_budgets,
    ring_hist,
    ring_hist_len,
    ring_caps,
    ring_count,
):
    """Up to ``k_units`` consecutive draft-verify units in ONE dispatch
    — the speculative device-resident loop (device residency v2).

    ``keys`` [S, k_units*width, 2] is each lane's flat step-key window
    from its NEXT emission number (a unit at in-loop emission count
    ``done`` consumes keys ``done..done+width-1`` — the same slice K=1
    verify dispatches would take); ``budgets`` [S] the remaining
    emission budgets at launch; ``hist``/``hist_len`` the right-aligned
    on-device drafting windows; ``draft_caps`` [S] the per-lane
    adaptive draft widths (data, not shape).  ``ring_*`` carry up to R
    pre-marshaled pending lanes activated in admission order when an
    occupied lane retires.  See :func:`_spec_loop_impl` for boundary
    semantics and the bit-exactness-with-K=1 argument.
    """

    def verify_fn(pk, pv, tbl, lens, alive, toks, widths, tmp, ukeys):
        return paged_verify_span(
            params, config, pick_fn, pk, pv, tbl, lens, alive, toks,
            widths, tmp, ukeys)

    return _spec_loop_impl(
        verify_fn, k_units, eos, max_order, redraft, width,
        pool_k, pool_v, tables, lengths, active, tokens, temps, keys,
        budgets, hist, hist_len, draft_caps, ring_tables, ring_lengths,
        ring_tokens, ring_temps, ring_keys, ring_budgets, ring_hist,
        ring_hist_len, ring_caps, ring_count)


def paged_mixed_verify_step(
    params,
    config: TransformerConfig,
    pick_fn,
    pool_k,
    pool_v,
    p_table,
    p_start,
    p_tokens,
    p_last_row,
    p_temp,
    p_key,
    d_tables,
    d_lengths,
    d_active,
    d_tokens,
    d_widths,
    d_temps,
    d_keys,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """The speculative twin of :func:`paged_mixed_step`: one fused
    dispatch runs a bounded prefill chunk for ONE filling slot AND a
    draft-verify chunk for every active decode lane.  Like the plain
    mixed step it is a pure composition of the two standalone entry
    points (prefill first, then the verify span) over disjoint writable
    blocks, so both sides' values — and therefore the emitted streams —
    are unchanged; only the dispatch count drops.  Returns
    (p_picked [1], picked [S, W], accepts [S], pool_k, pool_v).
    """
    p_logits, pk, pv = paged_prefill_step(
        params, config, pool_k, pool_v, p_table, p_start,
        jnp.ones_like(p_start, bool), p_tokens, p_last_row)
    with jax.named_scope("sample"):
        p_picked = pick_fn(p_logits, p_temp, p_key)
    picked, accepts, pk, pv = paged_verify_span(
        params, config, pick_fn, pk, pv, d_tables, d_lengths, d_active,
        d_tokens, d_widths, d_temps, d_keys)
    return p_picked, picked, accepts, pk, pv


def _mixed_first_step(params, config: TransformerConfig, pool_k, pool_v,
                      p_table, p_start, p_tokens, p_last_row, d_tables,
                      d_lengths, d_active, d_tokens, recurrent=None,
                      p_folded=None, p_slot=None, d_folded=None,
                      span: int = 1):
    """A mixed dispatch's fused first step: the chunk's ``W`` rows and every
    decode lane's row of the span's step 0, ``W + S`` rows through ONE
    layer loop — one pass over the weights where the chunk and the step
    made two.  The rows are two :class:`RowGroup` s of one lane
    (:func:`_attend_rows`): the embedding, the norms, the projections, rope
    by each row's own position, :func:`_write_rows`, the output projection
    and the feed-forward (a routed block's router and experts: ONE grouping
    over all the live rows, so an expert both groups touch is read once)
    run once over all of them, and only the attention runs a group at a
    time — the chunk's queries over ``p_table`` on the key-block loop, the
    lanes' one row each over ``d_tables`` through the paged kernel where it
    can run.  Both groups' K/V rows are written before either attends: the
    filling slot is no decode lane, the groups write disjoint blocks and
    share read-only prefix blocks only, so neither reads a row the other
    writes.

    A state BY SLOT (``recurrent``) parts the rows exactly there too: the
    chunk's rows read and leave the state of slot ``p_slot`` crossing them
    in order (a 'retention' chunk: from its fold point ``p_folded``, a tail
    that grows by the chunk's width), the lanes' one row each reads and
    leaves the state of its own slot (lane s IS slot s; a 'retention' lane
    from ``d_folded[s]``, and its tail's window is as wide as the whole
    dispatch needs — ``span`` rows more, as in every step of the scan that
    follows, so the dispatch reads one width of window and the folds stay
    where they were: after the span's last step).  The filling slot is no
    decode lane, so the two groups' states are disjoint as their blocks
    are.  A chunk of such a model pads FORWARD: its padding's rows land in
    the scratch block.

    Returns (logits [S + P, vocab] float32 — the lanes' rows, then
    each chunk's ``p_last_row`` — pool_k, pool_v, the routing counts or
    None, the :class:`Recurrent` or None): the head runs over those rows
    alone."""
    dtype = config.dtype
    bs = _page_rows(pool_k)
    lanes, width = p_tokens.shape
    p_positions = p_start[:, None] + jnp.arange(width)[None, :]  # [P, W]
    d_positions = d_lengths[:, None]  # [S, 1]

    def side_by_side(chunk, steps):  # [P, W], [S] -> [1, P * W + S]
        return jnp.concatenate([chunk.reshape(-1), steps])[None, :]

    positions = side_by_side(p_positions, d_lengths)
    d_blk = jnp.take_along_axis(
        d_tables, (d_lengths // bs)[:, None], axis=1)[:, 0]
    # an idle lane's row lands in the scratch block 0
    blk = side_by_side(
        jnp.take_along_axis(p_table, p_positions // bs, axis=1),
        jnp.where(d_active, d_blk, 0))
    x = params["embed"][side_by_side(p_tokens, d_tokens)].astype(dtype)
    if config.positional != "rope":
        x = x + params["pos_embed"][positions].astype(dtype)
    # a chunk's rows after its last real one are padding
    p_live = jnp.arange(width)[None, :] <= p_last_row[:, None]
    live = side_by_side(p_live, d_active)
    if recurrent is not None:
        # past the prompt's last row: into the scratch block, not a page
        blk = jnp.where(live, blk, 0)
    groups = (RowGroup(p_table, p_positions, p_live, p_folded, p_slot, width),
              RowGroup(d_tables, d_positions, d_active[:, None], d_folded,
                       None, span))
    # the groups say whose states the rows cross: nothing else is carried
    carried = None if recurrent is None else (recurrent,)
    x, pool_k, pool_v, counts, recurrent = _run_layers(
        params, config, pool_k, pool_v, groups, positions, blk,
        positions % bs, x, live, carried=carried)

    with jax.named_scope("lm_head"):
        last = jnp.arange(lanes) * width + p_last_row
        head_in = jnp.concatenate([x[0, lanes * width:], x[0, last]])
        head_in = _rms_norm(head_in, params["final_norm"]["scale"],
                            config.norm_eps)
        logits = (head_in
                  @ params["lm_head"].astype(dtype)).astype(jnp.float32)
    return logits, pool_k, pool_v, counts, recurrent


def mixed_weight_passes(span: int, back_to_back: bool) -> int:
    """The passes over the layer stack a mixed dispatch makes: the span's
    steps, and one more where the chunk has a pass of its own
    (``back_to_back``: :func:`paged_mixed_back_to_back`, and the sharded
    context's own composition)."""
    return span + 1 if back_to_back else span


def paged_mixed_step(
    params,
    config: TransformerConfig,
    pick_fn,
    span: int,
    eos,
    pool_k,
    pool_v,
    p_table,
    p_start,
    p_tokens,
    p_last_row,
    p_temp,
    p_key,
    d_tables,
    d_lengths,
    d_active,
    d_tokens,
    d_temps,
    d_keys,
    d_budgets,
    routing: bool = False,
    recurrent: Optional[Recurrent] = None,
    p_folded=None,
    p_slot=None,
    d_folded=None,
    decode_step=None,
) -> Tuple[jax.Array, ...]:
    """One fused mixed dispatch: a bounded prefill chunk for ONE
    filling slot + a full decode span for every active decode lane.

    This is the stall-free alternative to the engine's either/or step:
    under strict prefill priority every in-flight decode lane stalls
    for the full duration of every prompt chunk, so one long prompt
    spikes inter-token latency for ALL tenants.  Fusing the phases
    into one program keeps every decode lane advancing while the
    prompt fills, and pays ONE dispatch where the split path pays two.

    The chunk rides the span's FIRST pass over the weights
    (:func:`_mixed_first_step`: the chunk's rows and the lanes' first
    rows through one layer loop, the attention alone a group at a time),
    and the scan over the span's remaining ``span - 1`` steps follows,
    unchanged: ``span`` passes over the weights a dispatch, where the two
    entry points back to back make ``span + 1``
    (:func:`mixed_weight_passes`).  The fused step IS step 0 of the span:
    the lanes' pick under ``d_keys[:, 0]``, their lengths, budgets and EOS
    move as :func:`paged_decode_span`'s body moves them, and the chunk's
    first-token pick comes from the same head pass.  A row's values are
    what the split programs give up to the rounding of a sum over a batch
    of another height; the row positions, the masks, the keys consumed and
    what is written where are the same.

    A model with a state by slot (``recurrent``, the chunk's slot
    ``p_slot`` and, a 'retention' block's, the fold points ``p_folded`` /
    ``d_folded``) rides the same first step: its state phase runs a group
    of lanes at a time as the attention does, and the :class:`Recurrent`
    goes on through the scan and comes back last.  A 'retention' block's
    folds are the program's last phase, after the span's last step: the
    decode lanes' that are due (by the lanes that went IN active, as the
    span folds them), then the chunk's — the filling slot is no decode
    lane, so the order between them is free and each is what
    :func:`paged_mixed_back_to_back` folds, row for row.

    Returns (p_picked [1], emitted [span, S], pool_k, pool_v); ``p_picked``
    is meaningful only when the chunk is the prompt's final one (the
    fused first-token pick, same as the standalone prefill step); with
    ``routing`` the dispatch's routing counts come after, and the
    :class:`Recurrent` last.  ``decode_step``: the scan's step, as
    :func:`paged_decode_span` takes it.
    """
    logits, pk, pv, counts, recurrent = _mixed_first_step(
        params, config, pool_k, pool_v, p_table, p_start, p_tokens,
        p_last_row, d_tables, d_lengths, d_active, d_tokens, recurrent,
        p_folded, p_slot, d_folded, span)
    s = d_tokens.shape[0]
    with jax.named_scope("sample"):
        p_picked = pick_fn(logits[s:], p_temp, p_key)
        first = pick_fn(logits[:s], d_temps, d_keys[:, 0])
    emitted = first[None]
    # step 0's end, as paged_decode_span's body leaves it
    cont = d_active & (1 < d_budgets)
    if eos is not None:
        cont = cont & (first != eos)
    lens = d_lengths + d_active.astype(jnp.int32)
    if span > 1:
        # the scan's step j is the span's step j + 1: its budgets are one
        # emission short
        rest, pk, pv, recurrent, lens, more = _span_steps(
            params, config, pick_fn, span - 1, eos, pk, pv, d_tables, lens,
            cont, first, d_temps, d_keys[:, 1:], d_budgets - 1, routing,
            recurrent, d_folded, grow=span, decode_step=decode_step)
        emitted = jnp.concatenate([emitted, rest])
        if routing:
            counts = counts + more
    if recurrent is not None:
        recurrent = _settled(config, pk, pv, recurrent, d_tables, d_folded,
                             lens, d_active)
        recurrent = _settled(
            config, pk, pv, recurrent, p_table, p_folded,
            p_start + p_last_row + 1, jnp.ones_like(p_start, bool), p_slot)
    return _step_outputs(routing, counts, recurrent, p_picked, emitted, pk,
                         pv)


def paged_mixed_back_to_back(
    params,
    config: TransformerConfig,
    pick_fn,
    span: int,
    eos,
    pool_k,
    pool_v,
    p_table,
    p_start,
    p_tokens,
    p_last_row,
    p_temp,
    p_key,
    d_tables,
    d_lengths,
    d_active,
    d_tokens,
    d_temps,
    d_keys,
    d_budgets,
    routing: bool = False,
    recurrent: Optional[Recurrent] = None,
    p_folded=None,
    p_slot=None,
    d_folded=None,
) -> Tuple[jax.Array, ...]:
    """:func:`paged_mixed_step` as nothing but the two entry points run
    back to back — :func:`paged_prefill_step` on the prefill lane, then
    :func:`paged_decode_span` over the decode lanes, ``span + 1`` passes
    over the weights — so the per-row-position math is the split
    dispatches' op for op: the prefill lane writes only its own (fresh or
    CoW-private) blocks, every decode lane writes only its own current
    block, and the prefill-then-decode order inside the program matches
    the split scheduler's dispatch order.  A model's :class:`Recurrent`
    goes through the chunk (a 'retention' block's fold with it) and then
    the span (and its lanes' folds) — the chunk's slot is no lane of the
    span, so the span leaves what the chunk wrote there alone — and comes
    back last, after the chunk's and the span's routing counts, summed.
    No engine dispatches this: it is the REFERENCE the fused step is held
    to, token for token, state for state (``tests/test_mixed_fused.py``)."""
    p_logits, pk, pv, *p_counts = paged_prefill_step(
        params, config, pool_k, pool_v, p_table, p_start,
        jnp.ones_like(p_start, bool), p_tokens, p_last_row,
        routing=routing, recurrent=recurrent, folded=p_folded, slots=p_slot)
    if recurrent is not None:
        recurrent = p_counts.pop()
    with jax.named_scope("sample"):
        p_picked = pick_fn(p_logits, p_temp, p_key)
    emitted, pk, pv, *d_counts = paged_decode_span(
        params, config, pick_fn, span, eos, pk, pv,
        d_tables, d_lengths, d_active, d_tokens, d_temps, d_keys,
        d_budgets, routing=routing, recurrent=recurrent, folded=d_folded)
    if recurrent is not None:
        recurrent = d_counts.pop()
    counts = [p + d for p, d in zip(p_counts, d_counts)]
    return _step_outputs(routing, counts[0] if counts else None, recurrent,
                         p_picked, emitted, pk, pv)


# ---------------------------------------------------------------------------
# generation by diffusion over blocks (TransformerConfig.diffusion_block):
# a dispatch no longer yields one token a lane
# ---------------------------------------------------------------------------

def paged_diffusion_prefill(
    params,
    config: TransformerConfig,
    pool_k,
    pool_v,
    tables,
    starts,
    active,
    tokens,
    last_rows,
    routing: bool = False,
) -> Tuple[jax.Array, ...]:
    """:func:`paged_prefill_step` of a configuration that generates by
    diffusion over blocks: the chunk is whole blocks of the prompt
    (``starts`` and the real rows' count are multiples of
    ``diffusion_block``), written and attended under the block-causal
    mask, and yields NO token — the prompt's first generated block is
    denoised by the passes that follow — so no head is applied.
    Returns (pool_k, pool_v) and, with ``routing``, the counts."""
    _, pool_k, pool_v, counts, _ = _prefill_rows(
        params, config, pool_k, pool_v, tables, starts, active, tokens,
        last_rows)
    return _step_outputs(routing, counts, None, pool_k, pool_v)


def paged_diffusion_pass(
    params,
    config: TransformerConfig,
    pool_k,
    pool_v,
    tables,
    lengths,
    active,
    tokens,
    masked,
    open_rows,
    quota,
    routing: bool = False,
) -> Tuple[jax.Array, ...]:
    """One pass over every active lane's current block of B =
    ``config.diffusion_block`` rows: a denoising pass where the block
    still has masked rows, the commit pass where it has none.  The two
    are one program; what differs is what the engine does with it.

    ``lengths`` [S] is each lane's cached length, a multiple of B: its
    block sits at positions ``lengths[s] .. lengths[s] + B - 1``.
    ``tokens`` [S, B] holds what is known of it (the prompt's tail, the
    rows committed so far); a row that ``masked`` [S, B] says is still
    unknown takes ``config.mask_token``'s embedding instead, whatever id
    ``tokens`` holds there — masked-ness is the engine's position state,
    never ``token == mask_token`` (a prompt may hold that id, and a pick
    may be it).  The B rows' K/V are written at their positions first,
    then all B attend the lane's view up to the block's last row: they
    see one another and everything cached before.  Nothing moves
    ``lengths``: the next pass over the block overwrites these rows, and
    only the K/V of the pass over the FINISHED block are left standing,
    when the engine advances the lane past it.

    Every row's logits are its OWN token's (no shift).  In float32:
    ``picked`` [S, B] the argmax, its confidence the softmax probability
    of it, and ``commit`` [S, B] the rows this pass commits — of the
    rows ``open_rows`` [S, B] says may be committed (masked, and inside
    the request's budget), the ``quota[s]`` of highest confidence, ties
    to the lowest index.  The ``[S, B, vocab]`` logits stay on the
    device.  Returns (picked int32, commit bool, pool_k, pool_v) and,
    with ``routing``, the counts last.  Inactive lanes write the scratch
    block and commit nothing; their ``tables`` rows are the scratch block
    too (``engine._diffusion_lanes`` marshals zeros), which is how the
    paged kernel knows a lane is idle and reads nothing for it."""
    dtype = config.dtype
    b = tokens.shape[1]
    bs = pool_k.shape[3]
    positions = lengths[:, None] + jnp.arange(b)[None, :]  # [S, B]
    blk = jnp.take_along_axis(tables, positions // bs, axis=1)
    blk = jnp.where(active[:, None], blk, 0)
    off = positions % bs
    ids = jnp.where(masked, config.mask_token, tokens)
    x = params["embed"][ids].astype(dtype)  # [S, B, d]
    live = jnp.broadcast_to(active[:, None], positions.shape)
    x, pool_k, pool_v, counts, _ = _run_layers(
        params, config, pool_k, pool_v, tables, positions, blk, off, x,
        live)

    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["final_norm"]["scale"], config.norm_eps)
        logits = (x @ params["lm_head"].astype(dtype)).astype(jnp.float32)
    with jax.named_scope("denoise_pick"):
        picked = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        top = jnp.max(logits, axis=-1, keepdims=True)
        confidence = 1.0 / jnp.sum(jnp.exp(logits - top), axis=-1)
        may = open_rows & active[:, None]
        confidence = jnp.where(may, confidence, -jnp.inf)
        mine, other = confidence[:, :, None], confidence[:, None, :]
        row = jnp.arange(b)
        ahead = (other > mine) | (
            (other == mine) & (row[None, None, :] < row[None, :, None]))
        rank = jnp.sum(ahead, axis=-1, dtype=jnp.int32)
        commit = may & (rank < quota[:, None])
    return _step_outputs(routing, counts, None, picked, commit, pool_k,
                         pool_v)


def paged_mixed_diffusion_step(
    params,
    config: TransformerConfig,
    pool_k,
    pool_v,
    p_table,
    p_start,
    p_tokens,
    p_last_row,
    d_tables,
    d_lengths,
    d_active,
    d_tokens,
    d_masked,
    d_open,
    d_quota,
    routing: bool = False,
) -> Tuple[jax.Array, ...]:
    """The mixed flavour of the diffusion dispatch: one pass over every
    active lane's block and a block-causal prefill chunk for ONE filling
    slot — the two entry points above in one program, over disjoint
    writable blocks, as :func:`paged_mixed_step` composes its two.  The
    filling slot is no lane of the pass and shares only read-only prefix
    blocks with any, so neither side reads a row the other writes and
    their order is free: **the pass runs first**.  Compiled chunk-first
    the TPU compiler copies the whole pool four times (4.86 GB of
    temporaries at ``sdar-30b-a3b-chat``'s size, 24 MB this way round:
    ``tests/test_chip_compile.py``).  Returns (picked, commit, pool_k,
    pool_v) and, with ``routing``, the pass's and the chunk's counts
    summed."""
    picked, commit, pk, pv, *d_counts = paged_diffusion_pass(
        params, config, pool_k, pool_v, d_tables, d_lengths, d_active,
        d_tokens, d_masked, d_open, d_quota, routing=routing)
    pk, pv, *p_counts = paged_diffusion_prefill(
        params, config, pk, pv, p_table, p_start,
        jnp.ones_like(p_start, bool), p_tokens, p_last_row, routing=routing)
    return (picked, commit, pk, pv,
            *[p + d for p, d in zip(p_counts, d_counts)])
