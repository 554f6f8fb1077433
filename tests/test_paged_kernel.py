"""The dense block's decode lanes attend through the paged kernel
(``ops/paged_attention.paged_decode_attention`` through
``serving/paged._attend_view``).

Off the TPU the kernel runs in interpret mode.  It is held to
``_attend_cached`` over each lane's whole gathered view and to the
key-block loop it stands in for (``_attend_blocks``), in float32, at lane
lengths that straddle a page (16 rows) and a compute block (512), with
idle lanes beside live ones, under a window that binds, and a lane's
numbers to the bit whatever its neighbours hold.  One engine serves
through it (``paged._kernel_mode`` held to "interpret"): the loop's
greedy streams, and nothing compiles after warm-up.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models.decoding import _attend_blocks, _attend_cached
from kubeshare_tpu.models.transformer import (TransformerConfig,
                                              transformer_init)
from kubeshare_tpu.ops.paged_attention import (BLOCK_ROWS, kernel_fits,
                                               paged_decode_attention)
from kubeshare_tpu.serving import EngineConfig, Request, ServingEngine, paged
from kubeshare_tpu.utils import profiling

HEADS = {"mqa_16_1": (16, 1), "gqa_24_2": (24, 2)}  # the two cells' groups
PAGE, WIDTH, D, LAYERS, LAYER = 16, 96, 128, 2, 1  # a view of 1536 rows
LAST = PAGE * WIDTH  # a lane that holds its table's last row
IDLE = 0
POISON = -1
# rows a lane holds: either side of a page and of a compute block
LENGTHS = [1, 15, 16, 17, 511, 512, 513, LAST]


def _pool(heads, dtype=jnp.float32, width=WIDTH, lanes=6):
    """A pool whose every lane has ``width`` pages of its own, in a
    scattered order.  The scratch block 0 and the pool's last block
    (``POISON``: where a test points the table entries that must not be
    read) hold NaN: no live lane reads either."""
    h, h_kv = HEADS[heads]
    rng = np.random.default_rng(7)
    blocks = lanes * width + 1
    pool_k, pool_v = (
        jnp.asarray(rng.normal(size=(LAYERS, blocks + 1, h_kv, PAGE, D)),
                    dtype).at[:, (0, POISON)].set(jnp.nan) for _ in range(2))
    tables = rng.permutation(np.arange(1, blocks)).reshape(lanes, width)
    q = jnp.asarray(rng.normal(size=(lanes, h, D)), dtype)
    return q, pool_k, pool_v, tables.astype(np.int32)


def _lanes(tables, lengths):
    """Tables and positions of lanes holding ``lengths`` rows each; an
    ``IDLE`` lane's table row is the scratch block, as the engine
    marshals it."""
    lengths = np.asarray(lengths)
    tables = np.where(lengths[:, None] > 0, tables, 0)
    return jnp.asarray(tables), jnp.asarray(np.maximum(lengths - 1, 0),
                                            jnp.int32)


def _kernel(q, pool_k, pool_v, tables, positions, window=None):
    return paged_decode_attention(q, pool_k, pool_v, jnp.asarray(LAYER),
                                  tables, positions, window=window,
                                  interpret=True)


def _views(pool_k, pool_v, tables):
    lanes, width = tables.shape
    return (pool[LAYER][tables].transpose(0, 2, 1, 3, 4).reshape(
        lanes, pool.shape[2], width * PAGE, D) for pool in (pool_k, pool_v))


def _whole(q, pool_k, pool_v, tables, positions, window=None):
    view_k, view_v = _views(pool_k, pool_v, tables)
    return _attend_cached(q[:, :, None], view_k, view_v, positions[:, None],
                          window=window)[:, :, 0]


def _loop(q, pool_k, pool_v, tables, positions, window=None):
    view_k, view_v = _views(pool_k, pool_v, tables)

    def view_block(i):
        return tuple(jax.lax.dynamic_slice_in_dim(
            a, i * BLOCK_ROWS, BLOCK_ROWS, axis=2) for a in (view_k, view_v))

    # the scratch block's poison is in an idle lane's view alone
    return _attend_blocks(q[:, :, None], view_block, BLOCK_ROWS,
                          pool_k.shape[2], positions[:, None],
                          window)[:, :, 0]


@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("heads", list(HEADS))
def test_kernel_is_the_whole_view_and_the_loop(heads, length):
    """Lane 1 holds ``length`` rows between an idle lane and lanes of
    other reaches; every live lane reads what the whole view and the
    key-block loop give it, an idle lane reads zeros."""
    q, pool_k, pool_v, tables = _pool(heads)
    lengths = [IDLE, length, 700, IDLE, 33, LAST - length + 1]
    tables, positions = _lanes(tables, lengths)
    out = np.asarray(_kernel(q, pool_k, pool_v, tables, positions))
    live = np.asarray(lengths) > 0
    assert not np.isnan(out).any()
    for want in (_whole, _loop):
        np.testing.assert_allclose(
            out[live], np.asarray(want(q, pool_k, pool_v, tables,
                                       positions))[live], atol=2e-5, rtol=0)
    assert (out[~live] == 0).all()


@pytest.mark.parametrize("window", [5, 40, 600])
@pytest.mark.parametrize("heads", list(HEADS))
def test_a_window_that_binds(heads, window):
    """Under a window of 5 or 40 a lane at row 1100 sees nothing of its
    first two compute blocks, which are skipped, not read: the pages
    before a lane's window point to the poisoned block here.  A window of
    600 straddles two compute blocks."""
    q, pool_k, pool_v, tables = _pool(heads)
    lengths = [1101, 3, IDLE, 513, LAST, 41]
    tables, positions = _lanes(tables, lengths)
    want = np.asarray(_whole(q, pool_k, pool_v, tables, positions, window))
    unread = (np.arange(WIDTH)[None, :]
              < (np.asarray(positions)[:, None] - window + 1) // PAGE)
    poison = pool_k.shape[1] - 1
    out = np.asarray(_kernel(q, pool_k, pool_v,
                             jnp.where(unread, poison, tables), positions,
                             window))
    live = np.asarray(lengths) > 0
    assert unread[0].sum() == (1100 - window + 1) // PAGE
    assert not np.isnan(out).any()
    np.testing.assert_allclose(out[live], want[live], atol=2e-5, rtol=0)


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("heads", list(HEADS))
def test_a_lanes_numbers_do_not_depend_on_its_neighbours_reach(heads,
                                                               window):
    """Lane 1 holds 530 rows.  Whether its neighbours are idle, a page
    long or hold their tables' last rows, its output is the same to the
    bit: a lane walks its own pages alone."""
    q, pool_k, pool_v, tables = _pool(heads)
    outs = [np.asarray(_kernel(q, pool_k, pool_v,
                               *_lanes(tables, lengths), window))[1]
            for lengths in ([IDLE, 530, IDLE, IDLE, IDLE, IDLE],
                            [7, 530, 16, 1, IDLE, 2],
                            [LAST, 530, LAST, 1025, LAST, 512])]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


@pytest.mark.parametrize("heads", list(HEADS))
def test_pages_past_a_lanes_last_row_are_not_read(heads):
    """A lane walks its pages up to its own position: the table entries
    after them point to the poisoned block here."""
    q, pool_k, pool_v, tables = _pool(heads)
    lengths = [530, 16, 17, LAST, IDLE, 1]
    tables, positions = _lanes(tables, lengths)
    held = np.arange(WIDTH)[None, :] <= np.asarray(positions)[:, None] // PAGE
    poison = pool_k.shape[1] - 1
    out = np.asarray(_kernel(q, pool_k, pool_v,
                             jnp.where(held, tables, poison), positions))
    live = np.asarray(lengths) > 0
    assert not np.isnan(out).any()
    np.testing.assert_allclose(
        out[live], np.asarray(_whole(q, pool_k, pool_v, tables,
                                     positions))[live], atol=2e-5, rtol=0)


@pytest.mark.parametrize("heads", list(HEADS))
def test_the_served_dtype_and_a_table_narrower_than_a_compute_block(heads):
    """bfloat16, as the cells serve it (a group of 12 padded to a tile of
    16 sublanes), over a table of 20 pages: a compute block is the whole
    table.  The probabilities meet V in bfloat16, as in the loop."""
    q, pool_k, pool_v, tables = _pool(heads, jnp.bfloat16, width=20, lanes=4)
    lengths = [320, IDLE, 17, 200]
    tables, positions = _lanes(tables, lengths)
    out = np.asarray(_kernel(q, pool_k, pool_v, tables, positions))
    live = np.asarray(lengths) > 0
    want = np.asarray(_whole(q, pool_k, pool_v, tables, positions),
                      np.float32)
    np.testing.assert_allclose(out[live], want[live], atol=3e-2, rtol=0)


def test_what_the_kernel_can_read():
    """A K and a V row a head, rows of whole lanes, pages of whole tiles;
    not the latent block's packed rows, not a narrow head."""
    def pool(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    assert kernel_fits(pool(24, 8193, 1, 16, 128), pool(24, 8193, 1, 16, 128),
                       128)
    assert kernel_fits(pool(2, 9, 2, 8, 128, dtype=jnp.float32),
                       pool(2, 9, 2, 8, 128, dtype=jnp.float32), 128)
    assert not kernel_fits(pool(2, 9, 2, 8, 128), pool(2, 9, 2, 8, 128), 128)
    assert not kernel_fits(pool(8, 99, 1, 16, 512), pool(4, 99, 1, 16, 128),
                           128)
    assert not kernel_fits(pool(2, 9, 2, 16, 64), pool(2, 9, 2, 16, 64), 64)


# -- the engine through the kernel -------------------------------------------


def _model():
    config = TransformerConfig(
        vocab_size=64, d_model=256, n_heads=2, n_kv_heads=1, n_layers=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32, attention="reference",
        positional="rope")
    return transformer_init(jax.random.PRNGKey(0), config), config


def _engine(params, config):
    return ServingEngine(params, config, EngineConfig(
        num_slots=3, block_size=8, num_blocks=25, max_request_len=48,
        prefill_chunk=8))


def _streams(engine):
    rng = np.random.default_rng(31)
    for rid, prompt, new in (("long", 29, 9), ("s0", 5, 8), ("s1", 13, 4),
                             ("long2", 21, 6)):
        engine.submit(Request(rid, rng.integers(0, 64, prompt), new))
    return {rid: r.tokens for rid, r in engine.run().items()}


def _attended(since):
    me = threading.current_thread().name
    return {r[4]["attend"] for r in profiling.spans(
        since=since, name="kubeshare.engine.launch")
        if r[3] == me and r[4]["lanes"]}


def test_engine_through_the_kernel_serves_the_loops_streams(monkeypatch):
    """Lanes at different reaches in every dispatch, idle lanes beside
    them, long prompts filling meanwhile, key blocks of 8 rows (the
    tier-1 views are otherwise attended whole): the greedy streams are
    the key-block loop's, and nothing compiles after warm-up."""
    monkeypatch.setattr(paged, "KEY_BLOCK", 8)
    params, config = _model()
    since = time.monotonic()
    want = _streams(_engine(params, config))
    assert _attended(since) == {"blocks"}
    monkeypatch.setattr(paged, "_kernel_mode", lambda: "interpret")
    engine = _engine(params, config)
    engine.warmup()
    baseline = engine.compile_counts()
    since = time.monotonic()
    assert _streams(engine) == want
    assert engine.compile_counts() == baseline
    assert _attended(since) == {"kernel"}
