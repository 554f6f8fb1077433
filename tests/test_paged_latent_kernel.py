"""The latent blocks' decode lanes attend through the paged latent kernel
(``ops/paged_attention.paged_latent_decode_attention`` through
``serving/paged._latent_layers``): the latent row, one latent page a
sub-layer, two sub-layers' rotary keys packed in one row.

As ``tests/test_paged_kernel.py`` holds the dense block's kernel: in
interpret mode off the TPU, against each lane's whole gathered view and
the key-block loop, at lane lengths that straddle a page and a compute
block, idle lanes beside live ones, a lane's numbers to the bit whatever
its neighbours hold; and one engine of each routed kind serves the loop's
greedy streams through it, nothing compiling after warm-up.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from latent_kinds import KINDS, config_of, params_of  # noqa: E402
from paged_kernel_helpers import (  # noqa: E402
    IDLE, PAGE, POISON, ROUTING, _attended, _engine, _lanes, _streams, pool)

from kubeshare_tpu.models.transformer import (  # noqa: E402
    latent_absorbed, latent_attend, latent_attend_blocks, latent_scale)
from kubeshare_tpu.ops.paged_attention import (  # noqa: E402
    BLOCK_ROWS, kernel_fits, latent_kernel_fits,
    paged_latent_decode_attention)
from kubeshare_tpu.serving import paged  # noqa: E402

SUBS = 5  # an odd count, as joyai-llm-flash's: the third rotary row's
#           second half is spare
RANK, ROPE = 128, 64
L_WIDTH = 64  # a view of 1024 rows: two compute blocks
L_LAST = PAGE * L_WIDTH
L_LENGTHS = [1, 17, 511, 512, 513, L_LAST]
# the sub-layer attended: which half of which packed rotary row is its own
HALVES = {"first_half": 2, "second_half": 1, "beside_the_spare_half": 4}
LATENT_HEADS = [64, 32]  # longcat-flash-chat's, joyai-llm-flash's
WIDER = dict(kv_lora_rank=RANK, qk_rope_head_dim=ROPE)
BIG = 1e4  # what the other sub-layer's half of a rotary row holds here


def _latent_case(heads, dtype=jnp.float32, width=L_WIDTH, lanes=6,
                 other_half=BIG, sub=1):
    """A latent pool whose every lane has ``width`` pages of its own in a
    scattered order, one query row a lane, and a twin's attention weights
    at ``heads`` heads.  The scratch block and the pool's last block
    (``POISON``) hold NaN; the half of sub-layer ``sub``'s rotary row that
    is not its own holds ``other_half`` in every block."""
    changes = dict(WIDER, n_heads=heads)
    config = config_of("latent_moe", dtype, **changes)
    attn = params_of("latent_moe", 3, dtype, **changes)["layers"][1]["attn"]
    rng = np.random.default_rng(11)
    blocks = lanes * width + 1
    pool_c = jnp.asarray(
        rng.normal(size=(SUBS, blocks + 1, 1, PAGE, RANK)), dtype)
    pool_r = jnp.asarray(
        rng.normal(size=(-(-SUBS // 2), blocks + 1, 1, PAGE, 2 * ROPE)),
        dtype)
    other = (1 - sub % 2) * ROPE
    pool_r = pool_r.at[sub // 2, ..., other:other + ROPE].set(other_half)
    pool_c, pool_r = (pool.at[:, (0, POISON)].set(jnp.nan)
                      for pool in (pool_c, pool_r))
    tables = rng.permutation(np.arange(1, blocks)).reshape(lanes, width)
    q_nope = jnp.asarray(rng.normal(
        size=(lanes, heads, 1, config.qk_nope_head_dim)), dtype)
    q_rope = jnp.asarray(rng.normal(size=(lanes, heads, 1, ROPE)), dtype)
    return (config, attn, q_nope, q_rope, pool_c, pool_r,
            tables.astype(np.int32))


def _latent_kernel(config, attn, q_nope, q_rope, pool_c, pool_r, sub, tables,
                   positions):
    """The sub-layer's output [lanes, d_model] through the kernel."""
    return latent_absorbed(
        attn, q_nope,
        lambda q_abs: paged_latent_decode_attention(
            q_abs[:, :, 0], q_rope[:, :, 0], pool_c, pool_r, jnp.asarray(sub),
            tables, positions, scale=latent_scale(config),
            interpret=True)[:, :, None], config)[:, 0]


def _latent_views(pool_c, pool_r, sub, tables):
    """Each lane's whole view: its latent rows and ITS half of the packed
    rotary rows."""
    lanes, width = tables.shape
    own = sub % 2 * ROPE
    return (pool_c[sub][tables][:, :, 0].reshape(lanes, width * PAGE, RANK),
            pool_r[sub // 2][tables][:, :, 0, :, own:own + ROPE].reshape(
                lanes, width * PAGE, ROPE))


def _latent_whole(config, attn, q_nope, q_rope, pool_c, pool_r, sub, tables,
                  positions):
    view_c, view_r = _latent_views(pool_c, pool_r, sub, tables)
    return latent_attend(attn, q_nope, q_rope, view_c, view_r,
                         positions[:, None], config, absorbed=True)[:, 0]


def _latent_loop(config, attn, q_nope, q_rope, pool_c, pool_r, sub, tables,
                 positions):
    views = _latent_views(pool_c, pool_r, sub, tables)
    rows = min(BLOCK_ROWS, views[0].shape[1])  # a key block: it divides the view

    def view_block(i):
        return tuple(jax.lax.dynamic_slice_in_dim(a, i * rows, rows, axis=1)
                     for a in views)

    return latent_attend_blocks(attn, q_nope, q_rope, view_block, rows,
                                positions[:, None], config)[:, 0]


def _assert_latent_kernel_is(case, sub, lengths, wants, atol=2e-5,
                             kernel_tables=None):
    *model, tables = case
    tables, positions = _lanes(tables, lengths)
    out = np.asarray(_latent_kernel(
        *model, sub, tables if kernel_tables is None
        else kernel_tables(tables, positions), positions), np.float32)
    live = np.asarray(lengths) > 0
    assert not np.isnan(out).any()
    for want in wants:
        np.testing.assert_allclose(
            out[live], np.asarray(want(*model, sub, tables, positions),
                                  np.float32)[live], atol=atol, rtol=0)
    # an idle lane's context is zeros, and so is what is projected from it
    assert (out[~live] == 0).all()


@pytest.mark.parametrize("length", L_LENGTHS)
@pytest.mark.parametrize("heads", LATENT_HEADS)
def test_latent_kernel_is_the_whole_view_and_the_loop(heads, length):
    """Lane 1 holds ``length`` rows between an idle lane and lanes of
    other reaches; every live lane reads what ``latent_attend`` in the
    absorbed form over its whole view and the key-block loop give it, an
    idle lane reads zeros."""
    lengths = [IDLE, length, 700, IDLE, 33, L_LAST - length + 1]
    _assert_latent_kernel_is(_latent_case(heads), 1, lengths,
                             (_latent_whole, _latent_loop))


@pytest.mark.parametrize("half", list(HALVES))
@pytest.mark.parametrize("heads", LATENT_HEADS)
def test_each_sub_layer_reads_its_own_part_of_the_packed_rotary_row(heads,
                                                                    half):
    """A sub-layer whose rotary keys lie in the first half of a packed
    row, one in the second, and the last of an odd count, whose row's
    other half is spare: the other half (large finite values here) does
    not reach the scores."""
    sub = HALVES[half]
    assert (sub % 2, sub // 2) == {"first_half": (0, 1), "second_half":
                                   (1, 0), "beside_the_spare_half": (0, 2)
                                   }[half]
    lengths = [L_LAST, 511, IDLE, 513, 17, 512]
    _assert_latent_kernel_is(_latent_case(heads, sub=sub), sub, lengths,
                             (_latent_whole, _latent_loop))


@pytest.mark.parametrize("half", list(HALVES))
def test_the_other_sub_layers_rotary_keys_change_no_bit(half):
    """Whatever finite values the other half of the packed row holds, the
    sub-layer's output is the same to the bit: they meet zeros."""
    sub = HALVES[half]
    lengths = [3, 513, IDLE, L_LAST]
    outs = []
    for other_half in (0.0, BIG, -7.0):
        *model, tables = _latent_case(32, lanes=4, other_half=other_half,
                                      sub=sub)
        outs.append(np.asarray(_latent_kernel(*model, sub,
                                              *_lanes(tables, lengths))))
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


@pytest.mark.parametrize("heads", LATENT_HEADS)
def test_a_latent_lanes_numbers_do_not_depend_on_its_neighbours_reach(heads):
    """Lane 1 holds 530 rows.  Whether its neighbours are idle, a page
    long or hold their tables' last rows, its output is the same to the
    bit: a lane walks its own pages alone."""
    *model, tables = _latent_case(heads)
    outs = [np.asarray(_latent_kernel(*model, 1, *_lanes(tables, lengths)))[1]
            for lengths in ([IDLE, 530, IDLE, IDLE, IDLE, IDLE],
                            [7, 530, 16, 1, IDLE, 2],
                            [L_LAST, 530, L_LAST, 513, L_LAST, 512])]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


@pytest.mark.parametrize("heads", LATENT_HEADS)
def test_latent_pages_past_a_lanes_last_row_are_not_read(heads):
    """A lane walks its pages up to its own position: the table entries
    after them point to the poisoned block here."""
    def poisoned(tables, positions):
        held = np.arange(L_WIDTH)[None, :] \
            <= np.asarray(positions)[:, None] // PAGE
        return jnp.where(held, tables, tables.max() + 1)

    case = _latent_case(heads)
    assert np.isnan(np.asarray(case[4][0, case[-1].max() + 1])).all()
    _assert_latent_kernel_is(case, 1, [530, 16, 17, L_LAST, IDLE, 1],
                             (_latent_whole,), kernel_tables=poisoned)


@pytest.mark.parametrize("heads", LATENT_HEADS)
def test_the_latent_kernel_in_the_served_dtype(heads):
    """bfloat16, as the cells serve it, over a table of 20 pages (a
    compute block is the whole table): the weights meet the latent rows
    in bfloat16, as in the loop."""
    case = _latent_case(heads, jnp.bfloat16, width=20, lanes=4)
    _assert_latent_kernel_is(case, 1, [320, IDLE, 17, 200],
                             (_latent_whole, _latent_loop), atol=3e-2)


def test_what_the_latent_kernel_can_read():
    """One latent row a sub-layer and the rotary keys two to a row, both
    of whole 128-lane registers, pages of whole tiles: the two routed
    cells' pools; not a rank or a packed row that is no multiple of 128,
    not a page of half a tile, not a K and a V a head."""
    assert latent_kernel_fits(pool(8, 10923, 1, 16, 512),
                              pool(4, 10923, 1, 16, 128))
    assert latent_kernel_fits(pool(5, 17097, 1, 16, 512),
                              pool(3, 17097, 1, 16, 128))
    assert latent_kernel_fits(pool(2, 9, 1, 8, 128, dtype=jnp.float32),
                              pool(1, 9, 1, 8, 128, dtype=jnp.float32))
    assert not latent_kernel_fits(pool(8, 99, 1, 16, 192),
                                  pool(4, 99, 1, 16, 128))
    assert not latent_kernel_fits(pool(8, 99, 1, 16, 512),
                                  pool(4, 99, 1, 16, 64))
    assert not latent_kernel_fits(pool(4, 99, 1, 4, 16), pool(2, 99, 1, 4, 16))
    assert not latent_kernel_fits(pool(8, 99, 1, 8, 512),
                                  pool(4, 99, 1, 8, 128))
    assert not latent_kernel_fits(pool(24, 99, 1, 16, 128),
                                  pool(24, 99, 1, 16, 128))
    # and the dense kernel does not take the latent row
    assert not kernel_fits(pool(8, 99, 1, 16, 512), pool(4, 99, 1, 16, 128),
                           128)


# -- a latent engine of each kind through the kernel -------------------------


def _latent_engine(kind):
    return _engine(params_of(kind, 5, jnp.float32, **WIDER),
                   config_of(kind, jnp.float32, **WIDER))


@pytest.mark.parametrize("kind", list(KINDS))
def test_latent_engine_through_the_kernel_serves_the_loops_streams(
        monkeypatch, kind):
    """The twin of each routed cell, its cached row widened to what the
    kernel reads (a latent of 128, a rotary key of 64: two to a row of
    128), key blocks of 8 rows: lanes at different reaches in every
    dispatch, idle lanes beside them, long prompts filling meanwhile.
    The greedy streams and the routing counts are the key-block loop's,
    nothing compiles after warm-up, and the launch spans say ``kernel``
    wherever lanes decode, ``blocks`` for a chunk alone."""
    monkeypatch.setattr(paged, "KEY_BLOCK", 8)
    vocab = KINDS[kind].tc["vocab_size"]
    since = time.monotonic()
    loop = _latent_engine(kind)
    want = _streams(loop, vocab)
    assert _attended(since) == _attended(since, lanes=False) == {"blocks"}
    monkeypatch.setattr(paged, "_kernel_mode", lambda: "interpret")
    engine = _latent_engine(kind)
    engine.warmup()
    baseline = engine.compile_counts()
    since = time.monotonic()
    assert _streams(engine, vocab) == want
    assert engine.compile_counts() == baseline
    assert _attended(since) == {"kernel"}
    assert _attended(since, lanes=False) == {"blocks"}
    for counter in ROUTING:
        assert getattr(engine, counter) == getattr(loop, counter), counter
