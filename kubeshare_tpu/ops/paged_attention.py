"""Paged decode attention: a Pallas TPU kernel over the serving pool itself.

One query row a lane (the decode step) against that lane's OWN rows of
the stacked pool ``[layers, blocks, h_kv, block_size, d]``, read through
its block table.  The pool stays in HBM and is never transposed, windowed
or copied: ``pool.at[layer, page]`` is one contiguous ``[h_kv, block_size,
d]`` piece (4 KB at ``starcoderbase-1b``, 8 KB at ``starcoder2-3b``), and
the kernel copies a lane's pages into fast memory many a compute block
with asynchronous copies (started and waited for ``GROUP_PAGES`` at a
time: a copy a page costs 20-30 ns to issue, which is what bounds the
kernel at 16-row pages), the next compute block in flight while this one
is attended (across lanes too).  A lane costs what it holds: an idle lane
(its table row is the scratch block) does nothing, a live lane walks its
own pages up to its own position and, under a window, from the page the
window starts in.

The mathematics is ``models/decoding._attend_blocks``': scores in float32
scaled by ``d ** -0.5``, the causal band (and window), one running
softmax in float32, the probabilities cast to the pool's dtype before
they meet V, float32 context, normalised once.  Query heads are grouped
over their KV head without repeating K/V; a group that is no whole tile
of sublanes (12 of ``starcoder2-3b``'s 24 over 2) is padded with zero
rows outside the kernel and cut off after it.

JAX's ``jax.experimental.pallas.ops.tpu.paged_attention`` is the template
for the structure (scalar-prefetched tables, many page copies a compute
block, double buffering); its pool is ``[h_kv, pages, page, d]`` and its
grid walks lanes, heads and compute blocks, where this one is one
program that loops over the live lanes: a grid step a lane costs 0.35 us
whether the lane is live or not, and 1-2 lanes of 32 are.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 512  # view rows a compute block of the kernel holds
GROUP_PAGES = 8  # pages whose copies are started and waited for together


def sublanes(dtype) -> int:
    """Rows of one tile of ``dtype`` (8 of float32, 16 of bfloat16)."""
    return 32 // jnp.dtype(dtype).itemsize


def kernel_fits(pool_k, pool_v, head_dim: int) -> bool:
    """Whether the kernel can read this pool: a K and a V row a head
    (not the latent block's packed rows), rows of whole lanes and pages
    of whole tiles, so that a page lands in fast memory as it lies in
    the pool."""
    return (pool_k.shape == pool_v.shape and pool_k.dtype == pool_v.dtype
            and head_dim % 128 == 0
            and pool_k.shape[3] % sublanes(pool_k.dtype) == 0)


def _kernel(layer_ref, tables_ref, positions_ref, q_ref, k_hbm, v_hbm,
            o_ref, k_buf, v_buf, sems, next_ref, *, window, pages):
    lanes, h_kv, group, d = q_ref.shape
    # pages a wait: the most, up to GROUP_PAGES, that divide a compute block
    group_pages = max(g for g in range(1, GROUP_PAGES + 1) if pages % g == 0)
    width = tables_ref.shape[0] // lanes
    bs = k_buf.shape[3]
    rows = pages * bs
    layer = layer_ref[0]
    scale = d ** -0.5
    f32 = jnp.float32

    def live(lane):
        # block 0 is the scratch block: no live lane's view starts there
        return tables_ref[lane * width] != 0

    def first_page(lane):
        if window is None:
            return 0
        return jnp.maximum(positions_ref[lane] - window + 1, 0) // bs

    def groups_of(lane, block):
        """Compute block ``block`` of ``lane``: where its first page's
        entry lies in the tables, how many of its pages the lane holds,
        and in how many groups they are copied."""
        start = first_page(lane) + block * pages
        count = jnp.minimum(positions_ref[lane] // bs + 1 - start, pages)
        return (lane * width + start, count,
                (count + group_pages - 1) // group_pages)

    def start(lane, block, slot):
        """Start the copies of the pages the lane holds of the block, a
        group at a time; a group's surplus slots get the lane's last page
        again (their rows lie past its position), so every slot of a
        group holds rows of the lane's own."""
        entry, count, groups = groups_of(lane, block)

        def one(g, _):
            for j in range(group_pages):
                at = g * group_pages + j
                page = tables_ref[entry + jnp.minimum(at, count - 1)]
                pltpu.make_async_copy(k_hbm.at[layer, page],
                                      k_buf.at[slot, at],
                                      sems.at[slot, 0]).start()
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      v_buf.at[slot, at],
                                      sems.at[slot, 1]).start()
            return 0

        jax.lax.fori_loop(0, groups, one, 0)

    def wait(lane, block, slot):
        """Wait for those copies, a group at a wait: a wait takes as many
        bytes off the semaphore as its destination holds."""
        _, _, groups = groups_of(lane, block)
        some = pl.ds(0, group_pages)

        def one(g, _):
            for buf, sem in ((k_buf, 0), (v_buf, 1)):
                pltpu.make_async_copy(buf.at[slot, some], buf.at[slot, some],
                                      sems.at[slot, sem]).wait()
            return 0

        jax.lax.fori_loop(0, groups, one, 0)

    # next_ref[lane]: the next live lane after ``lane`` (``lanes``: none)
    def link(i, following):
        lane = lanes - 1 - i
        next_ref[lane] = following
        return jnp.where(live(lane), lane, following)

    first_live = jax.lax.fori_loop(0, lanes, link, lanes)

    # An idle lane's output and the rows of a compute block that no copy
    # fills are read (the latter under zero weights): neither may hold
    # what fast memory happened to.
    o_ref[...] = jnp.zeros_like(o_ref)
    k_buf[...] = jnp.zeros_like(k_buf)
    v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(first_live < lanes)
    def _():
        start(first_live, 0, 0)

    def attend_lane(lane, slot):
        position = positions_ref[lane]
        first = first_page(lane)
        blocks = (position // bs - first) // pages + 1
        following = next_ref[lane]

        def attend_block(block, carry):
            slot, state = carry

            @pl.when(block + 1 < blocks)
            def _():
                start(lane, block + 1, 1 - slot)

            @pl.when((block + 1 == blocks) & (following < lanes))
            def _():
                start(following, 0, 1 - slot)

            wait(lane, block, slot)
            k_pos = (first + block * pages) * bs + jax.lax.broadcasted_iota(
                jnp.int32, (group, rows), 1)
            valid = k_pos <= position
            if window is not None:
                valid = valid & (position - k_pos < window)
            new_state = []
            for head, (top, total, ctx) in enumerate(state):
                k = k_buf[slot, :, head].reshape(rows, d)
                v = v_buf[slot, :, head].reshape(rows, d)
                scores = jax.lax.dot_general(
                    q_ref[lane, head], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * scale
                scores = jnp.where(valid, scores, -jnp.inf)
                new_top = jnp.maximum(
                    top, jnp.max(scores, axis=-1, keepdims=True))
                weights = jnp.exp(scores - new_top)
                keep = jnp.exp(top - new_top)
                total = total * keep + jnp.sum(
                    weights, axis=-1, keepdims=True)
                ctx = ctx * keep + jnp.dot(
                    weights.astype(v.dtype), v, preferred_element_type=f32)
                new_state.append((new_top, total, ctx))
            return 1 - slot, tuple(new_state)

        # the lane's first compute block holds a row it sees (row 0, or
        # the row its window starts at): the maximum is finite from the
        # first block on
        state = tuple((jnp.full((group, 1), -jnp.inf, f32),
                       jnp.zeros((group, 1), f32),
                       jnp.zeros((group, d), f32)) for _ in range(h_kv))
        slot, state = jax.lax.fori_loop(0, blocks, attend_block,
                                        (slot, state))
        for head, (_, total, ctx) in enumerate(state):
            o_ref[lane, head] = (ctx / total).astype(o_ref.dtype)
        return slot

    def lane_step(lane, slot):
        return jax.lax.cond(live(lane), attend_lane, lambda _, slot: slot,
                            lane, slot)

    jax.lax.fori_loop(0, lanes, lane_step, 0)


@functools.partial(jax.jit, static_argnames=("window", "interpret"),
                   inline=True)
def paged_decode_attention(q, pool_k, pool_v, layer_idx, tables, positions,
                           window: Optional[int] = None,
                           interpret: bool = False):
    """Attention of one query row a lane, ``q`` [lanes, h, d], over each
    lane's rows ``0 .. positions[lane]`` of pool layer ``layer_idx``
    through ``tables`` [lanes, T]; returns the context [lanes, h, d],
    normalised in float32 and rounded once to ``q``'s dtype.  A lane
    whose table starts at the scratch block is idle and reads zeros.
    Jitted to be traced once for all the layers of a step program (the
    layer is an argument) and inlined, as the key-block loop it stands in
    for (``serving/paged._attend_view_blocks``)."""
    lanes, h, d = q.shape
    h_kv, bs = pool_k.shape[2], pool_k.shape[3]
    group = h // h_kv
    tile = sublanes(q.dtype)
    padded = -(-group // tile) * tile
    q = jnp.pad(q.reshape(lanes, h_kv, group, d),
                ((0, 0), (0, 0), (0, padded - group), (0, 0)))
    # a compute block is so many pages of the table; the table's width
    # bounds it
    pages = max(1, min(BLOCK_ROWS // bs, tables.shape[1]))
    buf = (2, pages, h_kv, bs, d)
    out = pl.pallas_call(
        functools.partial(_kernel, window=window, pages=pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM(buf, pool_k.dtype),
                            pltpu.VMEM(buf, pool_v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((lanes,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((lanes, h_kv, padded, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(layer_idx, (1,)).astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), positions.astype(jnp.int32),
      q, pool_k, pool_v)
    return out[:, :, :group].reshape(lanes, h, d)
