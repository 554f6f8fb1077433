"""The decode lanes attend through the paged kernels: the dense block's
here (``ops/paged_attention.paged_decode_attention`` through
``serving/paged._attend_view``), the latent blocks' in
``tests/test_paged_latent_kernel.py`` (the engines through the interpreted
kernel take 40-90 s each, and a tier-1 worker holds a file for its whole
length).

Off the TPU the kernel runs in interpret mode.  It is held to
``_attend_cached`` over each lane's whole gathered view and to the
key-block loop it stands in for (``_attend_blocks``), in float32, at lane
lengths that straddle a page (16 rows) and a compute block (512), with
idle lanes beside live ones, under a window that binds, and a lane's
numbers to the bit whatever its neighbours hold.  A lane's query is one
row (the two dense cells' groups) or one aligned diffusion block of 4 rows
under ``attend_reach`` (``sdar-30b-a3b-chat``'s group: 4 rows x 8 heads a
KV head).  One engine of each serves through it (``paged._kernel_mode``
held to "interpret"): the loop's greedy streams, and nothing compiles
after warm-up.
"""

import os
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from latent_kinds import REPO  # noqa: E402
from paged_kernel_helpers import (  # noqa: E402
    IDLE, PAGE, POISON, ROUTING, _attended, _engine, _lanes, _launches,
    _streams, pool)

from kubeshare_tpu.models.decoding import (  # noqa: E402
    _attend_blocks, _attend_cached)
from kubeshare_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, attend_reach, transformer_init)
from kubeshare_tpu.ops.paged_attention import (  # noqa: E402
    BLOCK_ROWS, kernel_fits, paged_decode_attention)
from kubeshare_tpu.serving import paged  # noqa: E402

# a lane's query group: query heads, KV heads, and the rows that share one
# reach (the two dense cells' one row; sdar-30b-a3b-chat's block of 4)
HEADS = {"mqa_16_1": (16, 1, 1), "gqa_24_2": (24, 2, 1),
         "gqa_32_4_block_4": (32, 4, 4)}
ONE_ROW = [heads for heads, group in HEADS.items() if group[2] == 1]
WIDTH, D, LAYERS, LAYER = 96, 128, 2, 1  # a view of 1536 rows
LAST = PAGE * WIDTH  # a lane that holds its table's last row
# rows a lane holds: either side of a page and of a compute block
LENGTHS = [1, 15, 16, 17, 511, 512, 513, LAST]


def _pool(heads, dtype=jnp.float32, width=WIDTH, lanes=6):
    """A pool whose every lane has ``width`` pages of its own, in a
    scattered order.  The scratch block 0 and the pool's last block
    (``POISON``: where a test points the table entries that must not be
    read) hold NaN: no live lane reads either.  ``q`` is [lanes, h, d]
    for one row a lane, [lanes, h, rows, d] for a block."""
    h, h_kv, rows = HEADS[heads]
    rng = np.random.default_rng(7)
    blocks = lanes * width + 1
    pool_k, pool_v = (
        jnp.asarray(rng.normal(size=(LAYERS, blocks + 1, h_kv, PAGE, D)),
                    dtype).at[:, (0, POISON)].set(jnp.nan) for _ in range(2))
    tables = rng.permutation(np.arange(1, blocks)).reshape(lanes, width)
    shape = (lanes, h, D) if rows == 1 else (lanes, h, rows, D)
    q = jnp.asarray(rng.normal(size=shape), dtype)
    return q, pool_k, pool_v, tables.astype(np.int32)


def _held(heads, lengths):
    """``lengths`` as lanes of this query group can hold them: a lane of a
    block holds whole blocks, its query the last of them."""
    rows = HEADS[heads][2]
    return [-(-length // rows) * rows for length in lengths]


def _kernel(q, pool_k, pool_v, tables, positions, window=None):
    return paged_decode_attention(q, pool_k, pool_v, jnp.asarray(LAYER),
                                  tables, positions, window=window,
                                  interpret=True)


def _views(pool_k, pool_v, tables):
    lanes, width = tables.shape
    return (pool[LAYER][tables].transpose(0, 2, 1, 3, 4).reshape(
        lanes, pool.shape[2], width * PAGE, D) for pool in (pool_k, pool_v))


def _reach(q, positions):
    """[lanes, C]: the last row each of a lane's query rows sees.  A block
    of C rows ends at ``positions``; ``attend_reach`` gives every row of
    it the block's last (a live lane holds whole blocks)."""
    if q.ndim == 3:
        return positions[:, None]
    rows = q.shape[2]
    block = SimpleNamespace(diffusion_block=rows)
    return attend_reach(block, jnp.maximum(
        positions[:, None] - (rows - 1) + jnp.arange(rows)[None, :], 0))


def _as(q, out):
    """``out`` [lanes, h, C, d] in ``q``'s shape."""
    return out[:, :, 0] if q.ndim == 3 else out


def _whole(q, pool_k, pool_v, tables, positions, window=None):
    view_k, view_v = _views(pool_k, pool_v, tables)
    return _as(q, _attend_cached(q.reshape(*q.shape[:2], -1, D), view_k,
                                 view_v, _reach(q, positions), window=window))


def _loop(q, pool_k, pool_v, tables, positions, window=None):
    view_k, view_v = _views(pool_k, pool_v, tables)

    def view_block(i):
        return tuple(jax.lax.dynamic_slice_in_dim(
            a, i * BLOCK_ROWS, BLOCK_ROWS, axis=2) for a in (view_k, view_v))

    # the scratch block's poison is in an idle lane's view alone
    return _as(q, _attend_blocks(q.reshape(*q.shape[:2], -1, D), view_block,
                                 BLOCK_ROWS, pool_k.shape[2],
                                 _reach(q, positions), window))


@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("heads", list(HEADS))
def test_kernel_is_the_whole_view_and_the_loop(heads, length):
    """Lane 1 holds ``length`` rows between an idle lane and lanes of
    other reaches; every live lane reads what the whole view and the
    key-block loop give it, an idle lane reads zeros."""
    q, pool_k, pool_v, tables = _pool(heads)
    lengths = _held(heads, [IDLE, length, 700, IDLE, 33, LAST - length + 1])
    tables, positions = _lanes(tables, lengths)
    out = np.asarray(_kernel(q, pool_k, pool_v, tables, positions))
    live = np.asarray(lengths) > 0
    assert out.shape == q.shape and not np.isnan(out).any()
    for want in (_whole, _loop):
        np.testing.assert_allclose(
            out[live], np.asarray(want(q, pool_k, pool_v, tables,
                                       positions))[live], atol=2e-5, rtol=0)
    assert (out[~live] == 0).all()


@pytest.mark.parametrize("window", [5, 40, 600])
@pytest.mark.parametrize("heads", ONE_ROW)
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
    """Lane 1 holds 530 rows (532 where it holds blocks of 4).  Whether
    its neighbours are idle, a page long or hold their tables' last rows,
    its output is the same to the bit: a lane walks its own pages alone."""
    q, pool_k, pool_v, tables = _pool(heads)
    outs = [np.asarray(_kernel(q, pool_k, pool_v,
                               *_lanes(tables, _held(heads, lengths)),
                               window))[1]
            for lengths in ([IDLE, 530, IDLE, IDLE, IDLE, IDLE],
                            [7, 530, 16, 1, IDLE, 2],
                            [LAST, 530, LAST, 1025, LAST, 512])]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


@pytest.mark.parametrize("heads", list(HEADS))
def test_pages_past_a_lanes_last_row_are_not_read(heads):
    """A lane walks its pages up to its own position — a block's last
    row: the table entries after them point to the poisoned block here."""
    q, pool_k, pool_v, tables = _pool(heads)
    lengths = _held(heads, [530, 16, 17, LAST, IDLE, 1])
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
    16 sublanes; a block's 32 rows two whole tiles), over a table of 20
    pages: a compute block is the whole table.  The probabilities meet V
    in bfloat16, as in the loop."""
    q, pool_k, pool_v, tables = _pool(heads, jnp.bfloat16, width=20, lanes=4)
    lengths = _held(heads, [320, IDLE, 17, 200])
    tables, positions = _lanes(tables, lengths)
    out = np.asarray(_kernel(q, pool_k, pool_v, tables, positions))
    live = np.asarray(lengths) > 0
    want = np.asarray(_whole(q, pool_k, pool_v, tables, positions),
                      np.float32)
    np.testing.assert_allclose(out[live], want[live], atol=3e-2, rtol=0)


def test_a_blocks_rows_see_one_another_and_the_rows_after_them():
    """The first row of a block sees the block's last: what the pass has
    just written there moves it, as it moves the whole view under
    ``attend_reach``; under the causal mask it would not.  The row after
    the block (the next block's, unwritten) moves nothing."""
    q, pool_k, pool_v, tables = _pool("gqa_32_4_block_4")
    lengths = [520, IDLE, 16, 4, LAST, 20]
    tables, positions = _lanes(tables, lengths)
    out = np.asarray(_kernel(q, pool_k, pool_v, tables, positions))
    live = np.asarray(lengths) > 0

    def rewritten(pool, at):
        """``pool`` with every live lane's row ``positions + at`` anew."""
        position = np.asarray(positions) + at
        pages = np.asarray(tables)[np.arange(len(lengths)),
                                   np.minimum(position // PAGE, WIDTH - 1)]
        return pool.at[LAYER, pages[live], :, (position % PAGE)[live]].add(3.0)

    last_row = rewritten(pool_k, 0), rewritten(pool_v, 0)
    moved = np.asarray(_kernel(q, *last_row, tables, positions))
    assert (np.abs(moved - out)[live][:, :, 0].max(axis=(1, 2)) > 1e-3).all()
    np.testing.assert_allclose(
        moved[live], np.asarray(_whole(q, *last_row, tables, positions))[live],
        atol=2e-5, rtol=0)
    causal = _attend_cached(
        q, *_views(*last_row, tables),
        positions[:, None] - 3 + jnp.arange(4)[None, :])
    assert np.abs(moved - np.asarray(causal))[live][:, :, 0].max() > 1e-3
    # lane 4 holds its table's last row: nothing lies after it
    after = live & (np.asarray(lengths) < LAST)
    next_row = rewritten(pool_k, 1), rewritten(pool_v, 1)
    np.testing.assert_array_equal(
        np.asarray(_kernel(q, *next_row, tables, positions))[after],
        out[after])


def test_what_the_kernel_can_read():
    """A K and a V row a head, rows of whole lanes, pages of whole tiles;
    not the latent block's packed rows, not a narrow head."""
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


def _diffusion_model():
    """The twin of ``sdar-30b-a3b-chat`` (generation by diffusion over
    blocks of 4, GQA 4 to 2), its head widened to what the kernel reads,
    on the benchmark's seeded weights in float32."""
    import json

    from chipbench import sdar_30b_a3b_chat_weights as weights

    with open(os.path.join(REPO, "chipbench", "tests", "configs",
                           "tiny_sdar.json")) as f:
        tc = {**json.load(f)["transformer_config"], "dtype": "float32",
              "head_width": 128}
    config = TransformerConfig(**{**tc, "dtype": jnp.float32})
    return weights.make_weights(11, tc), config


def test_diffusion_engine_through_the_kernel_serves_the_loops_streams(
        monkeypatch):
    """Every lane's block of 4 rows is one query group of the kernel: at
    key blocks of 8 rows (a view longer than a key block) the passes,
    alone and beside a chunk, serve the key-block loop's streams token for
    token and route as it does, nothing compiles after warm-up, and the
    launch spans of kind ``diffusion`` / ``mixed_diffusion`` say ``kernel``.
    A chunk alone says ``blocks`` — its rows are many blocks, of reaches
    that differ — but for a prompt's tail of one block, one reach again.
    At the tier-1 key block the same view is attended ``whole``, whatever
    the kernel could do."""
    params, config = _diffusion_model()
    vocab = config.mask_token  # prompts hold ordinary ids
    monkeypatch.setattr(paged, "_kernel_mode", lambda: "interpret")
    since = time.monotonic()
    whole = _streams(_engine(params, config), vocab)
    assert _attended(since) == {"whole"}
    monkeypatch.setattr(paged, "_kernel_mode", lambda: None)
    monkeypatch.setattr(paged, "KEY_BLOCK", 8)
    since = time.monotonic()
    loop = _engine(params, config)
    want = _streams(loop, vocab)
    assert want == whole
    assert _attended(since) == _attended(since, lanes=False) == {"blocks"}
    monkeypatch.setattr(paged, "_kernel_mode", lambda: "interpret")
    engine = _engine(params, config)
    engine.warmup()
    baseline = engine.compile_counts()
    since = time.monotonic()
    assert _streams(engine, vocab) == want
    assert engine.compile_counts() == baseline
    passes = [a for a in _launches(since) if a["lanes"]]
    assert {a["kind"] for a in passes} == {"diffusion", "mixed_diffusion"}
    assert {a["attend"] for a in passes} == {"kernel"}
    chunks = {(a["chunk"], a["attend"]) for a in _launches(since)
              if not a["lanes"]}
    assert chunks == {(8, "blocks"), (4, "kernel")}
    for counter in ROUTING:
        assert getattr(engine, counter) == getattr(loop, counter), counter
