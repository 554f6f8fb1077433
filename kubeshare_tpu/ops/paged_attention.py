"""Paged decode attention: Pallas TPU kernels over the serving pool itself.

The query rows of a lane that share one reach — one row (the decode
step), or the rows of one aligned diffusion block, which all see the
block's last row — against that lane's OWN rows of the stacked pool,
read through its block table.  The pool stays in HBM
and is never transposed, windowed or copied: a page of it is one
contiguous piece, and a kernel copies a lane's pages into fast memory
many a compute block with asynchronous copies (started and waited for
``GROUP_PAGES`` at a time: a copy a page costs 20-30 ns to issue, which
is what bounds the kernels at 16-row pages), the next compute block in
flight while this one is attended (across lanes too).  A lane costs what
it holds: an idle lane (its table row is the scratch block) does
nothing, a live lane walks its own pages up to its own position and,
under a window, from the page the window starts in.  That walk is
written once (:func:`_walk_pages`); the two kernels differ in the rows
they copy and in a compute block's mathematics:

- :func:`paged_decode_attention`, the dense block's ``[layers, blocks,
  h_kv, block_size, d]`` pool: ``pool.at[layer, page]`` is ``[h_kv,
  block_size, d]`` (4 KB at ``starcoderbase-1b``, 8 KB at
  ``starcoder2-3b``).  The mathematics is
  ``models/decoding._attend_blocks``': scores in float32 scaled by ``d **
  -0.5``, the causal band (and window), one running softmax in float32,
  the probabilities cast to the pool's dtype before they meet V, float32
  context, normalised once.  Query heads are grouped over their KV head
  without repeating K/V, and a lane's C rows of one reach with them: a
  query group is ``(h / h_kv) x C`` rows (8 x 4 of a diffusion block at
  ``sdar-30b-a3b-chat``, whose page is 16 KB); a group that is no whole
  tile of sublanes (12 of ``starcoder2-3b``'s 24 over 2) is padded with
  zero rows outside the kernel and cut off after it.
- :func:`paged_latent_decode_attention`, the latent blocks' pool
  (``kv_blocks.KVRowLayout`` "latent"): ``pool_c.at[sub, page]`` is one
  ``[1, block_size, kv_lora_rank]`` piece (16 KB at the two routed
  cells), ``pool_r.at[sub // 2, page]`` the rotary keys of two sub-layers
  side by side (4 KB).  The mathematics is
  ``models/transformer.latent_attend_blocks``' absorbed form: scores
  ``(q_abs . c + q_rope . r) x scale`` in float32, the same running
  softmax, and the latent page, read once, is the key and the value; all
  the heads are one query group over the single row.

JAX's ``jax.experimental.pallas.ops.tpu.paged_attention`` is the template
for the structure (scalar-prefetched tables, many page copies a compute
block, double buffering); its pool is ``[h_kv, pages, page, d]`` and its
grid walks lanes, heads and compute blocks, where these are one
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
    """Whether the dense block's kernel can read this pool: a K and a V
    row a head (the latent blocks' rows are :func:`latent_kernel_fits`'),
    rows of whole lanes and pages of whole tiles, so that a page lands
    in fast memory as it lies in the pool."""
    return (pool_k.shape == pool_v.shape and pool_k.dtype == pool_v.dtype
            and head_dim % 128 == 0
            and pool_k.shape[3] % sublanes(pool_k.dtype) == 0)


def latent_kernel_fits(pool_c, pool_r) -> bool:
    """Whether the latent kernel can read this pool: one latent row
    ``[1, block_size, kv_lora_rank]`` a sub-layer and the rotary keys of
    two sub-layers packed in one row (``kv_blocks.KVRowLayout``), both
    rows of whole 128-lane registers and pages of whole tiles."""
    return (pool_c.dtype == pool_r.dtype
            and pool_c.shape[1:4] == pool_r.shape[1:4]
            and pool_c.shape[2] == 1
            and pool_r.shape[0] == -(-pool_c.shape[0] // 2)
            and pool_c.shape[4] % 128 == 0 and pool_r.shape[4] % 128 == 0
            and pool_c.shape[3] % sublanes(pool_c.dtype) == 0)


def _softmax_block(scores, valid, top, total):
    """One compute block of the running softmax, float32: the scores the
    lane does not see masked, the running maximum and sum moved on.
    Returns (new maximum, new sum, what the context so far keeps, the
    block's weights)."""
    scores = jnp.where(valid, scores, -jnp.inf)
    new_top = jnp.maximum(top, jnp.max(scores, axis=-1, keepdims=True))
    weights = jnp.exp(scores - new_top)
    keep = jnp.exp(top - new_top)
    total = total * keep + jnp.sum(weights, axis=-1, keepdims=True)
    return new_top, total, keep, weights


def _walk_pages(tables_ref, positions_ref, next_ref, sems, copies, *, lanes,
                pages, window, first_state, attend_block, finish):
    """The page walk of both kernels: every live lane, in turn, walks its
    OWN pages from the one its window starts in up to the one its
    position lies in, ``pages`` a compute block, the next compute block
    (the next live lane's first, after a lane's last) in flight while
    this one is attended.

    ``copies`` are (``piece``, ``buf``) pairs: ``piece(page)`` the pool's
    piece of table entry ``page`` in HBM, ``buf`` ``[2, pages, ...]`` its
    two slots in fast memory; ``sems`` ``[2, len(copies)]``.  The
    mathematics is the caller's: ``first_state(lane)`` starts a lane's
    running softmax, ``attend_block(lane, slot, valid_of, state)`` moves
    it over the compute block in ``slot`` (``valid_of(m)`` the ``[m,
    rows]`` mask of the rows the lane sees) and ``finish(lane, state)``
    writes the lane's output."""
    # pages a wait: the most, up to GROUP_PAGES, that divide a compute block
    group_pages = max(g for g in range(1, GROUP_PAGES + 1) if pages % g == 0)
    width = tables_ref.shape[0] // lanes
    bs = copies[0][1].shape[3]
    rows = pages * bs

    def first_page(lane):
        if window is None:
            return 0
        return jnp.maximum(positions_ref[lane] - window + 1, 0) // bs

    def live(lane):
        # block 0 is the scratch block: no live lane's view starts there —
        # its view under a window, whose earlier entries may have been
        # handed back (a released entry points at the scratch block)
        return tables_ref[lane * width + first_page(lane)] != 0

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
                for i, (piece, buf) in enumerate(copies):
                    pltpu.make_async_copy(piece(page), buf.at[slot, at],
                                          sems.at[slot, i]).start()
            return 0

        jax.lax.fori_loop(0, groups, one, 0)

    def wait(lane, block, slot):
        """Wait for those copies, a group at a wait: a wait takes as many
        bytes off the semaphore as its destination holds."""
        _, _, groups = groups_of(lane, block)
        some = pl.ds(0, group_pages)

        def one(g, _):
            for i, (_, buf) in enumerate(copies):
                pltpu.make_async_copy(buf.at[slot, some], buf.at[slot, some],
                                      sems.at[slot, i]).wait()
            return 0

        jax.lax.fori_loop(0, groups, one, 0)

    # next_ref[lane]: the next live lane after ``lane`` (``lanes``: none)
    def link(i, following):
        lane = lanes - 1 - i
        next_ref[lane] = following
        return jnp.where(live(lane), lane, following)

    first_live = jax.lax.fori_loop(0, lanes, link, lanes)

    # The rows of a compute block that no copy fills are read, under zero
    # weights: they may not hold what fast memory happened to.
    for _, buf in copies:
        buf[...] = jnp.zeros_like(buf)

    @pl.when(first_live < lanes)
    def _():
        start(first_live, 0, 0)

    def attend_lane(lane, slot):
        position = positions_ref[lane]
        first = first_page(lane)
        blocks = (position // bs - first) // pages + 1
        following = next_ref[lane]

        def one_block(block, carry):
            slot, state = carry

            @pl.when(block + 1 < blocks)
            def _():
                start(lane, block + 1, 1 - slot)

            @pl.when((block + 1 == blocks) & (following < lanes))
            def _():
                start(following, 0, 1 - slot)

            wait(lane, block, slot)

            def valid_of(m):
                k_pos = (first + block * pages) * bs \
                    + jax.lax.broadcasted_iota(jnp.int32, (m, rows), 1)
                valid = k_pos <= position
                if window is not None:
                    valid = valid & (position - k_pos < window)
                return valid

            return 1 - slot, attend_block(lane, slot, valid_of, state)

        # the lane's first compute block holds a row it sees (row 0, or
        # the row its window starts at): the maximum is finite from the
        # first block on
        slot, state = jax.lax.fori_loop(0, blocks, one_block,
                                        (slot, first_state(lane)))
        finish(lane, state)
        return slot

    def lane_step(lane, slot):
        return jax.lax.cond(live(lane), attend_lane, lambda _, slot: slot,
                            lane, slot)

    jax.lax.fori_loop(0, lanes, lane_step, 0)


def _kernel(layer_ref, tables_ref, positions_ref, q_ref, k_hbm, v_hbm,
            o_ref, k_buf, v_buf, sems, next_ref, *, window, pages,
            scale=None):
    lanes, h_kv, group, d = q_ref.shape
    rows = pages * k_buf.shape[3]
    layer = layer_ref[0]
    scale = d ** -0.5 if scale is None else scale
    f32 = jnp.float32

    # an idle lane's output is read
    o_ref[...] = jnp.zeros_like(o_ref)

    def first_state(lane):
        return tuple((jnp.full((group, 1), -jnp.inf, f32),
                      jnp.zeros((group, 1), f32),
                      jnp.zeros((group, d), f32)) for _ in range(h_kv))

    def attend_block(lane, slot, valid_of, state):
        valid = valid_of(group)
        new_state = []
        for head, (top, total, ctx) in enumerate(state):
            k = k_buf[slot, :, head].reshape(rows, d)
            v = v_buf[slot, :, head].reshape(rows, d)
            scores = jax.lax.dot_general(
                q_ref[lane, head], k, (((1,), (1,)), ((), ())),
                preferred_element_type=f32) * scale
            top, total, keep, weights = _softmax_block(scores, valid, top,
                                                       total)
            ctx = ctx * keep + jnp.dot(
                weights.astype(v.dtype), v, preferred_element_type=f32)
            new_state.append((top, total, ctx))
        return tuple(new_state)

    def finish(lane, state):
        for head, (_, total, ctx) in enumerate(state):
            o_ref[lane, head] = (ctx / total).astype(o_ref.dtype)

    _walk_pages(tables_ref, positions_ref, next_ref, sems,
                ((lambda page: k_hbm.at[layer, page], k_buf),
                 (lambda page: v_hbm.at[layer, page], v_buf)),
                lanes=lanes, pages=pages, window=window,
                first_state=first_state, attend_block=attend_block,
                finish=finish)


def _pages_a_block(block_size: int, table_width: int) -> int:
    """Pages a compute block: ``BLOCK_ROWS`` rows of them; the table's
    width bounds it."""
    return max(1, min(BLOCK_ROWS // block_size, table_width))


def _paged_call(kernel, name: str, scalars, arrays, pools, out_shape,
                scratch_shapes, interpret: bool):
    """The one ``pallas_call`` of both kernels: ``scalars`` prefetched,
    ``arrays`` and the output whole in fast memory, the ``pools`` left
    in HBM, one program (the kernel loops over the live lanes itself)."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * len(arrays)
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*scalars, *arrays, *pools)


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret", "scale"),
                   inline=True)
def paged_decode_attention(q, pool_k, pool_v, layer_idx, tables, positions,
                           window: Optional[int] = None,
                           interpret: bool = False,
                           scale: Optional[float] = None):
    """Attention of the query rows of a lane that share ONE reach — ``q``
    [lanes, h, d], one row a lane (the decode step), or [lanes, h, C, d],
    C rows that see the same keys (a diffusion pass's aligned block) —
    over each lane's rows ``0 .. positions[lane]`` of pool layer
    ``layer_idx`` through ``tables`` [lanes, T]; returns the context in
    ``q``'s shape, normalised in float32 and rounded once to ``q``'s
    dtype.  A lane's C rows of the ``h / h_kv`` query heads over one KV
    head are one query group of the kernel, ``[lanes, h_kv, (h / h_kv) x
    C, d]`` (32 rows a KV head at ``sdar-30b-a3b-chat``: two whole bf16
    tiles), padded to whole tiles here and cut off after.  A lane whose
    table starts (under a ``window``: whose window starts) at the scratch block is idle and reads zeros.  ``scale``
    is what the scores are multiplied by (None: ``d ** -0.5``; heads
    narrower than the pool's row, laid into a part of a row of zeros, say
    their own: ``kv_blocks.KVRowLayout`` ``heads_paired``).
    Jitted to be traced once for all the layers of a step program (the
    layer is an argument) and inlined, as the key-block loop it stands in
    for (``serving/paged._attend_view_blocks``)."""
    one_row = q.ndim == 3
    if one_row:
        q = q[:, :, None]
    lanes, h, rows, d = q.shape
    h_kv, bs = pool_k.shape[2], pool_k.shape[3]
    group = h // h_kv * rows
    # a group that is no whole tile (7 query heads a KV head, 12) is padded
    # here, inside the call, and cut off after it
    tile = sublanes(q.dtype)
    padded = -(-group // tile) * tile
    q = jnp.pad(q.reshape(lanes, h_kv, group, d),
                ((0, 0), (0, 0), (0, padded - group), (0, 0)))
    pages = _pages_a_block(bs, tables.shape[1])
    buf = (2, pages, h_kv, bs, d)
    out = _paged_call(
        functools.partial(_kernel, window=window, pages=pages, scale=scale),
        "paged_decode_attention",
        (jnp.reshape(layer_idx, (1,)).astype(jnp.int32),
         tables.reshape(-1).astype(jnp.int32), positions.astype(jnp.int32)),
        (q,), (pool_k, pool_v),
        jax.ShapeDtypeStruct((lanes, h_kv, padded, d), q.dtype),
        [pltpu.VMEM(buf, pool_k.dtype), pltpu.VMEM(buf, pool_v.dtype),
         pltpu.SemaphoreType.DMA((2, 2)), pltpu.SMEM((lanes,), jnp.int32)],
        interpret)
    out = out[:, :, :group].reshape(lanes, h, rows, d)
    return out[:, :, 0] if one_row else out


def _latent_kernel(layers_ref, tables_ref, positions_ref, q_abs_ref,
                   q_rope_ref, c_hbm, r_hbm, o_ref, c_buf, r_buf, ctx_ref,
                   sems, next_ref, *, scale, pages):
    lanes, heads, rank = q_abs_ref.shape
    rows = pages * c_buf.shape[3]
    sub, packed_row = layers_ref[0], layers_ref[1]
    f32 = jnp.float32
    contract_last = (((1,), (1,)), ((), ()))

    # an idle lane's output is read
    o_ref[...] = jnp.zeros_like(o_ref)

    def first_state(lane):
        ctx_ref[...] = jnp.zeros_like(ctx_ref)
        return jnp.full((heads, 1), -jnp.inf, f32), jnp.zeros((heads, 1), f32)

    def attend_block(lane, slot, valid_of, state):
        # the latent page is read once: it is the key and the value
        c = c_buf[slot, :, 0].reshape(rows, rank)
        r = r_buf[slot, :, 0].reshape(rows, r_buf.shape[4])
        scores = (jax.lax.dot_general(q_abs_ref[lane], c, contract_last,
                                      preferred_element_type=f32)
                  + jax.lax.dot_general(q_rope_ref[lane], r, contract_last,
                                        preferred_element_type=f32)) * scale
        top, total, keep, weights = _softmax_block(scores, valid_of(heads),
                                                   *state)
        ctx_ref[...] = ctx_ref[...] * keep + jnp.dot(
            weights.astype(c.dtype), c, preferred_element_type=f32)
        return top, total

    def finish(lane, state):
        o_ref[lane] = (ctx_ref[...] / state[1]).astype(o_ref.dtype)

    _walk_pages(tables_ref, positions_ref, next_ref, sems,
                ((lambda page: c_hbm.at[sub, page], c_buf),
                 (lambda page: r_hbm.at[packed_row, page], r_buf)),
                lanes=lanes, pages=pages, window=None,
                first_state=first_state, attend_block=attend_block,
                finish=finish)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"),
                   inline=True)
def paged_latent_decode_attention(q_abs, q_rope, pool_c, pool_r, sub, tables,
                                  positions, scale: float,
                                  interpret: bool = False):
    """Latent attention in the absorbed form of one query row a lane over
    each lane's rows ``0 .. positions[lane]`` of sub-layer ``sub``:
    ``q_abs`` [lanes, H, kv_lora_rank] (the key up-projection folded in)
    against the latent rows of ``pool_c[sub]``, ``q_rope`` [lanes, H,
    rope] against the sub-layer's part of the packed rotary row
    (``pool_r[sub // packed]``), scores float32 times ``scale``; the
    latent rows are the values too.  Returns the context [lanes, H,
    kv_lora_rank], normalised in float32 and rounded once to ``q_abs``'s
    dtype; an idle lane reads zeros.  The H heads are one query group
    over the single row; which part of the packed row is the sub-layer's
    is settled here, outside the kernel: ``q_rope`` lies in that part of
    a row of zeros as wide as the packed one, so the kernel multiplies
    whole rows and the other sub-layer's keys meet zeros.  Jitted and
    inlined as :func:`paged_decode_attention`: traced once a program,
    the sub-layer an argument."""
    lanes, heads, rank = q_abs.shape
    rope = q_rope.shape[2]
    bs, packed_width = pool_r.shape[3], pool_r.shape[4]
    sub = jnp.asarray(sub, jnp.int32)
    packed = packed_width // rope
    q_row = jax.lax.dynamic_update_slice_in_dim(
        jnp.zeros((lanes, heads, packed_width), q_rope.dtype), q_rope,
        sub % packed * rope, axis=2)
    tile = sublanes(q_abs.dtype)
    padded = -(-heads // tile) * tile
    q_abs, q_row = (jnp.pad(q, ((0, 0), (0, padded - heads), (0, 0)))
                    for q in (q_abs, q_row))
    pages = _pages_a_block(bs, tables.shape[1])
    out = _paged_call(
        functools.partial(_latent_kernel, scale=scale, pages=pages),
        "paged_latent_decode_attention",
        (jnp.stack([sub, sub // packed]),
         tables.reshape(-1).astype(jnp.int32), positions.astype(jnp.int32)),
        (q_abs, q_row), (pool_c, pool_r),
        jax.ShapeDtypeStruct((lanes, padded, rank), q_abs.dtype),
        [pltpu.VMEM((2, pages, 1, bs, rank), pool_c.dtype),
         pltpu.VMEM((2, pages, 1, bs, packed_width), pool_r.dtype),
         pltpu.VMEM((padded, rank), jnp.float32),
         pltpu.SemaphoreType.DMA((2, 2)), pltpu.SMEM((lanes,), jnp.int32)],
        interpret)
    return out[:, :heads]
