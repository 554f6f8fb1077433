"""The dense block attends a long view a key block at a time, as far as
the lanes reach (``models/decoding._attend_blocks`` through
``serving/paged._attend_view``).

The function is held to ``_attend_cached`` in float32, and a lane's
numbers to the bit whatever its neighbours hold.  The engine is held on
the block path with the key block forced to 8 rows (the tier-1 engines'
views, 24-320 rows, are otherwise attended whole): the dense cache's
tokens, mixed against split, span against step, no compile after
warm-up, and the counters that say how far the attention went.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models.decoding import (_attend_blocks, _attend_cached,
                                           greedy_decode)
from kubeshare_tpu.models.transformer import (TransformerConfig,
                                              transformer_init)
from kubeshare_tpu.serving import EngineConfig, Request, ServingEngine, paged
from kubeshare_tpu.utils import profiling

KEY_BLOCK = 8
VIEW = 64  # rows: 8 key blocks
HEADS = {"mha": (4, 4), "mqa": (4, 1), "gqa": (6, 2)}
# per-lane first positions: a lane at row 0, ragged reaches, one that ends
# in the middle of a block (row 27 of block 3) and one in the last block
STARTS = {"decode": [0, 7, 8, 27, 63], "chunk": [0, 3, 16, 27, 59]}


def _case(heads, kind, seed=0):
    h, h_kv = HEADS[heads]
    starts = np.asarray(STARTS[kind])
    cq = 1 if kind == "decode" else 5
    rng = np.random.default_rng(seed)
    b, d = len(starts), 16
    q = jnp.asarray(rng.normal(size=(b, h, cq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h_kv, VIEW, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h_kv, VIEW, d)), jnp.float32)
    positions = jnp.asarray(starts[:, None] + np.arange(cq)[None, :])
    return q, k, v, positions


def _blocks(q, k, v, positions, window=None):
    def view_block(i):
        return tuple(jax.lax.dynamic_slice_in_dim(
            a, i * KEY_BLOCK, KEY_BLOCK, axis=2) for a in (k, v))

    return jax.jit(lambda q, positions: _attend_blocks(
        q, view_block, KEY_BLOCK, k.shape[1], positions, window))(
            q, positions)


@pytest.mark.parametrize("kind", ["decode", "chunk"])
@pytest.mark.parametrize("heads", list(HEADS))
def test_blocks_are_the_whole_view(heads, kind):
    q, k, v, positions = _case(heads, kind)
    np.testing.assert_allclose(
        _blocks(q, k, v, positions), _attend_cached(q, k, v, positions),
        atol=1e-5, rtol=0)


@pytest.mark.parametrize("window", [3, 8, 13])
def test_a_leading_block_wholly_masked_by_the_window(window):
    """A query at row 27 under a window of 3 sees nothing of blocks 0-2:
    the running maximum of a wholly masked block must not turn the
    carry into NaN."""
    q, k, v, positions = _case("gqa", "chunk")
    out = _blocks(q, k, v, positions, window)
    assert not np.isnan(out).any()
    np.testing.assert_allclose(
        out, _attend_cached(q, k, v, positions, window=window),
        atol=1e-5, rtol=0)


@pytest.mark.parametrize("window", [None, 5])
def test_a_lanes_numbers_do_not_depend_on_its_neighbours_reach(window):
    """Lane 1 holds 8 rows.  Whether its neighbours end in block 0 or in
    block 7 — one trip of the loop or eight — its output is the same to
    the bit: a block past a lane's reach is an exact no-op for it."""
    q, k, v, _ = _case("gqa", "decode")
    near = jnp.asarray([[2], [7], [5], [1], [6]])
    far = jnp.asarray([[63], [7], [40], [17], [62]])
    np.testing.assert_array_equal(
        _blocks(q, k, v, near, window)[1], _blocks(q, k, v, far, window)[1])


def test_blocks_past_the_furthest_lane_are_not_read():
    """The loop stops where the furthest lane's last row lies: rows from
    the next key block on may hold anything (NaN times a zero weight
    would still be NaN)."""
    q, k, v, positions = _case("mqa", "chunk")
    positions = jnp.minimum(positions, 29)  # the furthest row: block 3
    poison = jnp.arange(VIEW)[None, None, :, None] >= 4 * KEY_BLOCK
    out = _blocks(q, jnp.where(poison, jnp.nan, k),
                  jnp.where(poison, jnp.nan, v), positions)
    np.testing.assert_allclose(out, _attend_cached(q, k, v, positions),
                               atol=1e-5, rtol=0)


# -- the engine on the block path -------------------------------------------

CONFIGS = {
    "mha": dict(),
    "gqa_rope": dict(n_kv_heads=2, positional="rope"),
    "windowed": dict(attention_window=6),
    "moe": dict(moe_every=2, moe_num_experts=4, moe_top_k=2),
}


@pytest.fixture
def key_block(monkeypatch):
    monkeypatch.setattr(paged, "KEY_BLOCK", KEY_BLOCK)


def _model(name):
    config = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32, attention="reference",
        **CONFIGS[name])
    return transformer_init(jax.random.PRNGKey(0), config), config


def _engine(params, config, **overrides):
    kwargs = dict(num_slots=3, block_size=4, num_blocks=41,
                  max_request_len=48, prefill_chunk=8)
    kwargs.update(overrides)
    return ServingEngine(params, config, EngineConfig(**kwargs))


def _streams(engine, requests):
    for request in requests:
        engine.submit(Request(**request))
    return {rid: r.tokens for rid, r in engine.run().items()}


def _workload(sampled):
    rng = np.random.default_rng(31)
    requests = [
        dict(rid="long", prompt=rng.integers(0, 64, 29), max_new_tokens=9),
        dict(rid="s0", prompt=rng.integers(0, 64, 5), max_new_tokens=8),
        dict(rid="s1", prompt=rng.integers(0, 64, 13), max_new_tokens=4),
        dict(rid="long2", prompt=rng.integers(0, 64, 21), max_new_tokens=6),
    ]
    if sampled:  # the key schedule has to survive fusion and spans too
        requests.append(dict(
            rid="samp", prompt=rng.integers(0, 64, 13), max_new_tokens=7,
            temperature=0.8, rng=jax.random.PRNGKey(41)))
    return requests


@pytest.mark.parametrize("name", list(CONFIGS))
def test_block_path_serves_the_dense_caches_tokens(key_block, name):
    params, config = _model(name)
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (13,), 0, 64), np.int32)
    dense = np.asarray(greedy_decode(
        params, config, jnp.asarray(prompt)[None], 8))[0]
    engine = _engine(params, config)
    assert engine._key_block_rows == KEY_BLOCK
    assert _streams(engine, [dict(rid="r0", prompt=prompt,
                                  max_new_tokens=8)])["r0"] == list(dense)


@pytest.mark.parametrize("name", list(CONFIGS))
def test_block_path_mixed_against_split_and_span_against_step(key_block,
                                                              name):
    """One workload, long prompts filling while other lanes decode, lanes
    at different reaches in every dispatch: the fused dispatch, the split
    dispatches and one decode step a dispatch emit the same streams."""
    params, config = _model(name)
    sampled = name == "gqa_rope"
    pick = dict(top_k=10, top_p=0.95) if sampled else {}
    mixed = _engine(params, config, mixed=True, **pick)
    want = _streams(mixed, _workload(sampled))
    assert mixed.mixed_steps > 0
    split = _engine(params, config, mixed=False, **pick)
    assert _streams(split, _workload(sampled)) == want
    assert split.mixed_steps == 0
    step = _engine(params, config, decode_span=1, **pick)
    assert _streams(step, _workload(sampled)) == want


def test_block_path_compiles_nothing_after_warmup(key_block):
    params, config = _model("gqa_rope")
    engine = _engine(params, config)
    engine.warmup()
    baseline = engine.compile_counts()
    assert engine.view_rows_configured == 0  # warm-up is no dispatch
    rng = np.random.default_rng(5)
    for i in range(8):
        engine.submit(Request(f"r{i}", rng.integers(0, 64, 17 + i),
                              int(rng.integers(1, 6))))
    engine.run()
    assert engine.compile_counts() == baseline


def _fits_the_kernel():
    """A model and an engine the paged kernel can read: a head of 128
    lanes, pages of 8 rows of float32."""
    config = TransformerConfig(
        vocab_size=64, d_model=256, n_heads=2, n_kv_heads=1, n_layers=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32, attention="reference",
        positional="rope")
    params = transformer_init(jax.random.PRNGKey(0), config)
    return _engine(params, config, block_size=8, num_blocks=25)


@pytest.mark.parametrize("attend", ["blocks", "whole", "kernel"],
                         ids=["key_block_8", "whole_view", "paged_kernel"])
def test_view_row_counters_read_what_the_lanes_held(monkeypatch, attend):
    """Every planned dispatch adds the view's width to ``configured``,
    its furthest lane's rows, rounded up to key blocks, to ``reached``
    and its decode lanes' own rows to ``held``; the launch span carries
    the lanes' rows and what their attention ran (``rows``, ``attend``:
    what the kernels' roofline reads).  A view no longer than one key block is attended whole:
    reached is configured.  Through the paged kernel (interpreted here)
    the decode lanes read what they hold; a chunk with no lane beside it
    still runs the key-block loop."""
    forced = attend != "whole"
    if forced:
        monkeypatch.setattr(paged, "KEY_BLOCK", KEY_BLOCK)
    if attend == "kernel":
        monkeypatch.setattr(paged, "_kernel_mode", lambda: "interpret")
        engine = _fits_the_kernel()
    else:
        params, config = _model("gqa_rope")
        engine = _engine(params, config)
    # what each planned launch held, stated here apart from the engine: a
    # decode lane reaches its new row, a prefill chunk its end
    reaches, launch = [], engine._launch

    def watched(plan, fn, args):
        if plan is not None:
            reaches.append(max(
                [s.length + 1 for s in plan.decode_slots]
                + [sum(plan.chunk[:2]) if plan.chunk else 0]))
        return launch(plan, fn, args)

    engine._launch = watched
    since = time.monotonic()
    _streams(engine, _workload(False))
    launches = [r[4] for r in profiling.spans(
        since=since, name="kubeshare.engine.launch")
        if r[3] == threading.current_thread().name]
    planned = [a for a in launches if a["kind"] not in ("copy", "upload")]
    assert len(planned) == len(reaches) and all(
        1 <= reach <= 48 for reach in reaches)
    # the first dispatch is a prompt's first chunk: it reaches its end
    assert planned[0]["kind"] == "prefill" \
        and reaches[0] == planned[0]["chunk"]
    assert all(reach >= a["chunk"] for reach, a in zip(reaches, planned))
    block = KEY_BLOCK if forced else 48
    assert engine._key_block_rows == block
    assert engine.view_rows_configured == 48 * len(planned)
    assert engine.view_rows_reached == sum(
        -(-reach // block) * block for reach in reaches)
    assert (engine.view_rows_reached < engine.view_rows_configured) == forced
    # what the decode lanes ran, and the rows they held
    assert {a["attend"] for a in planned if a["lanes"]} == {attend}
    assert {a["attend"] for a in planned if not a["lanes"]} \
        == {"whole" if attend == "whole" else "blocks"}
    assert engine.view_rows_held == sum(a["rows"] for a in planned) > 0
    families = {f.name: f for f in engine.collect_metrics()}
    by_kind = {s.labels["kind"]: s.value for s in families[
        "kubeshare_serving_view_rows_total"].samples}
    assert by_kind == {"reached": engine.view_rows_reached,
                       "configured": engine.view_rows_configured,
                       "held": engine.view_rows_held}
