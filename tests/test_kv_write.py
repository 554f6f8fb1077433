"""``paged._write_rows`` against a plain reference, row by row.

A latent pool packs the rotary keys of two consecutive sub-layers into one
128-wide row of its V array (``kv_blocks.KVRowLayout.v_packed``).  A packed
row is written WHOLE — read, the sub-layer's own lanes replaced, scattered
back — because a scatter through the 64-wide part alone becomes, on the TPU,
a loop of one update a row (PERF.md, PR 42).  What that must not change: the
K array, the half of every row that means something, and every row that was
not addressed; and the unpacked layouts (a K and a V a head) must run the
parent's expression untouched.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.serving.paged import _v_part, _write_rows

BLOCKS, BLOCK_SIZE, RANK, ROPE = 7, 4, 16, 8


def _pools(rng, layers, heads=1, k_width=RANK, v_width=ROPE, packed=2):
    """A pool already full of values nobody may disturb."""
    v_layers = -(-layers // packed)
    k = rng.standard_normal((layers, BLOCKS, heads, BLOCK_SIZE, k_width))
    v = rng.standard_normal(
        (v_layers, BLOCKS, heads, BLOCK_SIZE, v_width * packed))
    return (jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16))


def _rows(rng, shape, heads, k_width, v_width):
    k = rng.standard_normal(shape + (heads, k_width))
    v = rng.standard_normal(shape + (heads, v_width))
    return jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16)


def _reference(pool_k, pool_v, layer, blk, off, k, v, packed):
    """Row by row, in order: a later row of one ``(blk, off)`` stands."""
    pool_k, pool_v = np.array(pool_k), np.array(pool_v)
    width = v.shape[-1]
    lo = layer % packed * width
    blk, off = np.asarray(blk).reshape(-1), np.asarray(off).reshape(-1)
    k = np.asarray(k).reshape((-1,) + k.shape[-2:])
    v = np.asarray(v).reshape((-1,) + v.shape[-2:])
    for i, (b, o) in enumerate(zip(blk, off)):
        pool_k[layer, b, :, o, :] = k[i]
        pool_v[layer // packed, b, :, o, lo:lo + width] = v[i]
    return pool_k, pool_v


def _distinct(rng, shape):
    """Distinct (blk, off) outside the scratch block, as live rows are."""
    n = int(np.prod(shape))
    flat = rng.permutation((BLOCKS - 1) * BLOCK_SIZE)[:n] + BLOCK_SIZE
    return ((flat // BLOCK_SIZE).astype(np.int32).reshape(shape),
            (flat % BLOCK_SIZE).astype(np.int32).reshape(shape))


def _same(a, b):
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))


PACKED_CASES = {
    # rows' shape, pool layers, the layers written in turn
    "decode_rows_even_sublayer": ((5, 1), 4, [2]),
    "decode_rows_odd_sublayer": ((5, 1), 4, [3]),
    "chunk_rows_even_sublayer": ((1, 12), 4, [0]),
    "chunk_rows_odd_sublayer": ((1, 12), 4, [1]),
    "odd_count_spare_half": ((1, 9), 5, [4]),
    "pair_in_sequence": ((1, 10), 4, [2, 3]),
    "pair_in_sequence_decode": ((6, 1), 5, [0, 1]),
    "every_sublayer_odd_count": ((3, 1), 5, [0, 1, 2, 3, 4]),
}


@pytest.mark.parametrize("case", sorted(PACKED_CASES))
def test_packed_rows_equal_the_reference(case):
    """Distinct live rows: both arrays equal the reference bit for bit —
    the written halves, the halves beside them (the pair's other key or
    the page's old contents) and every row not addressed."""
    shape, layers, written = PACKED_CASES[case]
    rng = np.random.default_rng(sorted(PACKED_CASES).index(case))
    pool_k, pool_v = _pools(rng, layers)
    assert pool_v.shape == (-(-layers // 2), BLOCKS, 1, BLOCK_SIZE, 2 * ROPE)
    want_k, want_v = np.array(pool_k), np.array(pool_v)
    blk, off = _distinct(rng, shape)
    write = jax.jit(_write_rows, static_argnums=2)
    for layer in written:
        k, v = _rows(rng, shape, 1, RANK, ROPE)
        pool_k, pool_v = write(pool_k, pool_v, layer, blk, off, k, v)
        want_k, want_v = _reference(want_k, want_v, layer, blk, off, k, v, 2)
    _same(pool_k, want_k)
    _same(pool_v, want_v)


@pytest.mark.parametrize("shape", [(6, 1), (1, 12)], ids=["decode", "chunk"])
@pytest.mark.parametrize("layer", [2, 3], ids=["even", "odd"])
def test_dead_rows_that_share_a_slot(shape, layer):
    """Idle lanes and a chunk's padding all write the scratch block's one
    slot: the live rows and everything not addressed are the reference's,
    the other half of the shared slot keeps what it held, and its written
    half holds one of the dead rows (which one is nobody's to rely on)."""
    rng = np.random.default_rng(layer)
    pool_k, pool_v = _pools(rng, 4)
    blk, off = _distinct(rng, shape)
    dead = np.zeros(shape, bool).reshape(-1)
    dead[[1, 3, 4]] = True
    dead = dead.reshape(shape)
    blk, off = np.where(dead, 0, blk), np.where(dead, 0, off)
    k, v = _rows(rng, shape, 1, RANK, ROPE)
    got_k, got_v = jax.jit(_write_rows, static_argnums=2)(
        pool_k, pool_v, layer, blk, off, k, v)
    want_k, want_v = _reference(pool_k, pool_v, layer, blk, off, k, v, 2)
    got_k, got_v = np.array(got_k, np.float32), np.array(got_v, np.float32)
    want_k = want_k.astype(np.float32)
    want_v = want_v.astype(np.float32)
    lo = layer % 2 * ROPE
    slot = got_v[layer // 2, 0, 0, 0].copy()
    dead_v = np.asarray(v, np.float32).reshape(-1, ROPE)[dead.reshape(-1)]
    assert any((slot[lo:lo + ROPE] == row).all() for row in dead_v)
    np.testing.assert_array_equal(
        np.delete(slot, np.s_[lo:lo + ROPE]),
        np.delete(np.asarray(pool_v, np.float32)[layer // 2, 0, 0, 0],
                  np.s_[lo:lo + ROPE]))
    dead_k = np.asarray(k, np.float32).reshape(-1, RANK)[dead.reshape(-1)]
    assert any((got_k[layer, 0, 0, 0] == row).all() for row in dead_k)
    for got, want, at in ((got_k, want_k, layer), (got_v, want_v, layer // 2)):
        got[at, 0, :, 0], want[at, 0, :, 0] = 0, 0
        np.testing.assert_array_equal(got, want)


def _parent_write_rows(pool_k, pool_v, layer_idx, blk, off, k, v):
    """``_write_rows`` as it stood before a packed row was written whole
    (PR 41): the expression every unpacked layout must still run."""
    if blk.ndim == 2 and blk.shape[1] == 1:
        blk, off, k, v = blk[:, 0], off[:, 0], k[:, 0], v[:, 0]
    blk, off = blk[..., None], off[..., None]
    v_layer, lanes = _v_part(pool_v, layer_idx, v.shape[-1])
    return (pool_k.at[layer_idx, blk, jnp.arange(pool_k.shape[2]), off,
                      :].set(k),
            pool_v.at[v_layer, blk, jnp.arange(pool_v.shape[2]), off,
                      lanes].set(v))


UNPACKED_CASES = {
    # rows' shape, KV heads
    "mqa_decode": ((5, 1), 1),
    "mqa_chunk": ((1, 12), 1),
    "gqa_decode": ((5, 1), 2),
    "gqa_chunk": ((1, 12), 2),
    "gqa_block_of_rows": ((3, 4), 4),
}


@pytest.mark.parametrize("case", sorted(UNPACKED_CASES))
def test_unpacked_rows_run_the_parent_expression(case):
    """A K and a V a head (``v_packed`` 1): the same program as the
    parent's expression — one scatter an array, no read of the pool — and
    the same values, the reference's."""
    shape, heads = UNPACKED_CASES[case]
    rng = np.random.default_rng(heads)
    pool_k, pool_v = _pools(rng, 3, heads, RANK, RANK, packed=1)
    blk, off = _distinct(rng, shape)
    k, v = _rows(rng, shape, heads, RANK, RANK)
    args = (pool_k, pool_v, 1, blk, off, k, v)
    assert _v_part(pool_v, 1, RANK) == (1, slice(None))
    mine = jax.make_jaxpr(_write_rows, static_argnums=2)(*args)
    parents = jax.make_jaxpr(_parent_write_rows, static_argnums=2)(*args)
    assert str(mine) == str(parents)
    assert "gather" not in str(mine)
    got = jax.jit(_write_rows, static_argnums=2)(*args)
    want = jax.jit(_parent_write_rows, static_argnums=2)(*args)
    reference = _reference(*args, packed=1)
    for g, w, r in zip(got, want, reference):
        _same(g, w)
        _same(g, r)


def test_a_packed_row_is_written_through_its_whole_width():
    """The mechanism, where a CPU can see it: the packed write reads the
    rows it is about to write (one gather) and scatters updates as wide as
    the V array's row; the parent's expression scattered the part."""
    rng = np.random.default_rng(0)
    pool_k, pool_v = _pools(rng, 4)
    blk, off = _distinct(rng, (1, 12))
    k, v = _rows(rng, (1, 12), 1, RANK, ROPE)

    def scatter_updates(fn):
        jaxpr = jax.make_jaxpr(fn, static_argnums=2)(
            pool_k, pool_v, 3, blk, off, k, v)
        pools = (pool_k.shape, pool_v.shape)
        return [eqn.invars[2].aval.shape[-1] for eqn in jaxpr.jaxpr.eqns
                if eqn.primitive.name == "scatter"
                and eqn.invars[0].aval.shape in pools], str(jaxpr)

    widths, text = scatter_updates(_write_rows)
    assert widths == [RANK, 2 * ROPE] and text.count("gather") == 1
    widths, text = scatter_updates(_parent_write_rows)
    assert widths == [RANK, ROPE] and "gather" not in text
