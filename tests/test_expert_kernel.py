"""The routed experts' tiles as one grouped kernel (``ops/moe.py``
``grouped_experts``), in interpret mode, held to the loop it stands in for
(``routed_experts_apply`` with no kernel mode): the three routed cells'
expert shapes cut small — one expert-parallel rank of a router with
zero-compute experts (``longcat-flash-chat``), 256 sigmoid-scored experts
under a choice bias (``joyai-llm-flash``), 128 softmax-scored, renormalised
(``sdar-30b-a3b-chat``) — over a decode step's, a pass's and a chunk's rows,
with dead rows, experts no row chose and experts with more rows than a tile.
The router, the liveness, the grouping and the six counts are one code above
the choice, so the counts are equal and the outputs differ by summation
order alone.  What chooses (``expert_path``: the backend's kernel mode and
``expert_kernel_fits``) and the tile's rows (``expert_tile_rows``) have cases
of their own, and a routed engine names the path on its launch spans.
"""

import logging
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from latent_kinds import KINDS, config_of, params_of
from kubeshare_tpu.isolation import ExecutionGuard
from kubeshare_tpu.ops import moe as moe_ops
from kubeshare_tpu.ops.moe import (EXPERT_TILE, MIN_TILE, ROUTING_COUNTS,
                                   expert_kernel_fits, expert_path,
                                   expert_tile_rows, expert_width_block,
                                   routed_experts_apply)
from kubeshare_tpu.serving import EngineConfig, Request, ServingEngine, paged
from kubeshare_tpu.serving import engine as engine_module
from kubeshare_tpu.utils import profiling

HELD, ZERO, ABSENT, TOUCHED, TILES, TILE_ROWS = range(len(ROUTING_COUNTS))
D, F = 128, 256

# the three cells' expert layers, cut small: (experts held, the first held,
# routed experts, zero-compute experts, the router's law)
SHAPES = {
    "rank_of_768": (16, 8, 64, 32, dict(top_k=12, scale=6.0)),
    "sigmoid_256": (256, 0, 256, 0, dict(
        top_k=8, scale=2.5, scoring="sigmoid", renormalise=True)),
    "softmax_128": (128, 0, 128, 0, dict(top_k=8, scale=1.0,
                                         renormalise=True)),
}


def _layer(shape, dtype=jnp.float32, seed=3, f=F):
    held, first, routed, zero, law = SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)

    def normal(key, dims, fan_in):
        return (jax.random.normal(key, dims, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    moe = {"router": normal(keys[0], (D, routed + zero), D) * 4,
           "w_gate": normal(keys[1], (held, D, f), D),
           "w_up": normal(keys[2], (held, D, f), D),
           "w_down": normal(keys[3], (held, f, D), f)}
    if law.get("scoring") == "sigmoid":  # the choice bias
        moe["bias"] = jax.random.normal(keys[4], (routed + zero,)) * 0.05
    return moe, dict(n_routed=routed, first_held=first, **law)


def _rows(n, dtype=jnp.float32, seed=5):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, D),
                             jnp.float32).astype(dtype)


def _both(moe, law, y, live=None):
    """(the loop's, the kernel's) outputs and counts."""
    loop = routed_experts_apply(moe, y, live=live, **law)
    kernel = routed_experts_apply(moe, y, live=live, kernel_mode="interpret",
                                  **law)
    return loop, kernel


@pytest.mark.parametrize("n", [32, 128, 513])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_is_the_loop(shape, n):
    """Equal counts, outputs equal to float32 rounding, with one row in
    five dead (an idle lane, a chunk's padding): a dead row reads 0 on
    either path and is in no tile."""
    moe, law = _layer(shape)
    y = _rows(n)
    live = jnp.arange(n) % 5 != 2
    (want, counts), (got, kernel_counts) = _both(moe, law, y, live)
    assert list(np.asarray(counts)) == list(np.asarray(kernel_counts))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert not np.asarray(got)[~np.asarray(live)].any()
    counts = np.asarray(counts)
    chose = int(live.sum()) * law["top_k"]
    assert counts[HELD] + counts[ZERO] + counts[ABSENT] == chose
    tile = expert_tile_rows(n, law["top_k"], moe["router"].shape[1])
    assert counts[TILE_ROWS] == counts[TILES] * tile >= counts[HELD] > 0
    assert counts[TILES] >= counts[TOUCHED]
    if n == 32 and shape != "rank_of_768":
        # experts no row chose: they are in no tile, and are not read
        assert counts[TOUCHED] < moe["w_gate"].shape[0]
    if shape == "rank_of_768":
        assert counts[ZERO] > 0 and counts[ABSENT] > 0


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_is_the_loop_in_bfloat16(shape):
    """As the cells serve it: bf16 rows and matrices, the two products
    and the hidden rounded to bf16 (where the loop's program rounds on
    the chip; on the CPU it rounds SiLU's steps too); the outputs differ
    by the last place of a bf16 sum (values of 2-4: 0.016-0.031)."""
    moe, law = _layer(shape, jnp.bfloat16)
    (want, counts), (got, kernel_counts) = _both(moe, law,
                                                 _rows(128, jnp.bfloat16))
    assert got.dtype == want.dtype == jnp.bfloat16
    assert list(np.asarray(counts)) == list(np.asarray(kernel_counts))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.07)


def _pinned(moe, chosen):
    """The router with every row's (positive) scores pinned on
    ``chosen``."""
    router = np.asarray(moe["router"], np.float32) * 1e-3
    router[:, chosen] += np.linspace(2.0, 1.0, len(chosen))[None, :]
    pinned = {**moe, "router": jnp.asarray(router)}
    if "bias" in moe:
        pinned["bias"] = jnp.zeros_like(moe["bias"])
    return pinned


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_an_expert_with_more_rows_than_a_tile(shape):
    """Every row sent to the same experts: each gets all 128 rows, many
    tiles of its own in a row (the second finds the expert where the
    first left it), nothing is dropped, and the other experts are in no
    tile."""
    moe, law = _layer(shape)
    first, top_k = law["first_held"], law["top_k"]
    last = first + moe["w_gate"].shape[0]
    # half of a row's choices among the held experts' first, the other
    # half below the first held (absent) or, with all held, the last
    chosen = [first + 1 + 2 * i for i in range(top_k // 2)] \
        + [(first or last) - 1 - i for i in range(top_k - top_k // 2)]
    held = sum(1 for e in chosen if first <= e < last)
    y = jnp.abs(_rows(128)) / 4  # pinned logits of 25-50: no score is 0
    (want, counts), (got, kernel_counts) = _both(_pinned(moe, chosen), law, y)
    tile = expert_tile_rows(128, top_k, moe["router"].shape[1])
    assert tile < 128
    assert list(np.asarray(kernel_counts)) == list(np.asarray(counts))
    assert list(np.asarray(counts)) == [
        128 * held, 0, 128 * (top_k - held), held, held * 128 // tile,
        held * 128]
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("mode", [None, "interpret"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_rows_result_is_its_own(shape, mode):
    """Routing is per row, nothing has a capacity, and a row's choices
    are added in a fixed order: a row reads the same, bit for bit,
    whatever rides beside it — other rows, fewer rows, dead rows — as
    long as the tile is the same (it follows from shapes).  On the loop
    and on the kernel alike."""
    moe, law = _layer(shape)
    y = _rows(128)
    run = lambda rows, live=None: np.asarray(routed_experts_apply(
        moe, rows, live=live, kernel_mode=mode, **law)[0])
    whole = run(y)
    others = run(y.at[1::2].set(_rows(64, seed=9)))
    np.testing.assert_array_equal(others[0::2], whole[0::2])
    assert np.abs(others[1::2] - whole[1::2]).max() > 1e-3
    alone = run(y, jnp.arange(128) == 77)
    np.testing.assert_array_equal(alone[77], whole[77])
    assert not np.delete(alone, 77, axis=0).any()


def test_the_width_in_blocks_is_the_width_whole(monkeypatch):
    """Where two copies of an expert do not fit fast memory the grid also
    walks blocks of its width (``longcat-flash-chat``'s 75 MB an expert):
    a tile's down products add up over the blocks, an idle grid step
    stays on the last live tile's last block, and the result is the
    loop's."""
    moe, law = _layer("rank_of_768")
    y = _rows(128)
    assert expert_width_block(moe) == F
    want, counts = routed_experts_apply(moe, y, **law)
    monkeypatch.setattr(moe_ops, "EXPERT_VMEM_BYTES", 2 * 3 * D * 128 * 4)
    assert expert_width_block(moe) == 128 and expert_kernel_fits(moe, 128)
    jax.clear_caches()  # the jitted kernel was traced for the whole width
    try:
        got, kernel_counts = routed_experts_apply(
            moe, y, kernel_mode="interpret", **law)
    finally:
        jax.clear_caches()
    assert list(np.asarray(counts)) == list(np.asarray(kernel_counts))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_no_live_row_is_no_tile():
    """Every row dead (a dispatch whose lanes are all idle): no tile on
    either path, zeros out, and the kernel's grid computes nothing."""
    moe, law = _layer("softmax_128")
    for mode in (None, "interpret"):
        out, counts = routed_experts_apply(
            moe, _rows(32), live=jnp.zeros((32,), bool), kernel_mode=mode,
            **law)
        assert not np.asarray(out).any() and not np.asarray(counts).any()


@pytest.mark.parametrize("n,top_k,outputs,tile", [
    (128, 8, 128, 16),  # sdar's pass: 8 rows an expert, not a tile of 128
    (512, 8, 128, 64),  # its chunk: 32
    (32, 8, 256, MIN_TILE),  # joyai's decode step: 1
    (512, 8, 256, 32),  # its chunk: 16
    (512, 12, 768, 16),  # lcf's chunk: 8 a held expert, of 768 outputs
    (32, 12, 768, MIN_TILE),
    (513, 8, 128, 128),  # 33 an expert: twice that, as a power of two
    (4096, 8, 128, EXPERT_TILE),  # never over the matrix unit's rows
    (1, 1, 8, MIN_TILE), (0, 8, 128, MIN_TILE),
    (300, 4, 16, EXPERT_TILE), (24, 4, 16, 16)])
def test_a_tile_follows_the_rows_an_expert_expects(n, top_k, outputs, tile):
    assert expert_tile_rows(n, top_k, outputs) == tile
    assert tile & (tile - 1) == 0 and MIN_TILE <= tile <= EXPERT_TILE


def _shapes(e, d, f, dtype=jnp.bfloat16, **changes):
    shaped = lambda *dims: jax.ShapeDtypeStruct(dims, dtype)
    return {"w_gate": shaped(e, d, f), "w_up": shaped(e, d, f),
            "w_down": shaped(e, f, d), **changes}


def test_the_kernel_fits_the_cells_experts_and_not_every_shape():
    """The three cells' experts fit, over a chunk's 512 rows too: ``sdar``
    / ``joyai`` whole (9.44 MB an expert, twice), ``lcf`` in 512-column
    blocks.  Widths that are no whole 128-lane registers (the tiny
    twins' 64), matrices of different dtypes or shapes, an expert so wide
    that no block fits, or rows so many that they and their result do
    not stay in fast memory beside the matrices, do not — and whatever
    does not fit, or has no backend for a kernel, runs the loop."""
    small, lcf = _shapes(128, 2048, 768), _shapes(16, 6144, 2048)
    assert expert_width_block(small) == 768
    assert expert_width_block(_shapes(256, 2048, 768)) == 768
    assert expert_width_block(lcf) == 512
    assert expert_width_block(_shapes(16, 6144, 2048, jnp.float32)) == 256
    for n in (32, 128, 512, 2048):
        assert expert_kernel_fits(small, n)
    assert not expert_kernel_fits(small, 4096)  # 134 MB of rows and result
    assert expert_kernel_fits(lcf, 32) and expert_kernel_fits(lcf, 512)
    assert not expert_kernel_fits(lcf, 1024)
    assert not expert_kernel_fits(_shapes(16, 64, 128), 32)
    assert not expert_kernel_fits(_shapes(16, 128, 64), 32)
    assert not expert_kernel_fits(_shapes(16, 128, 192), 32)
    assert not expert_kernel_fits(_shapes(4, 1 << 18, 128), 32)
    assert not expert_kernel_fits(_shapes(
        16, 128, 256, w_up=jax.ShapeDtypeStruct((16, 128, 128),
                                                jnp.bfloat16)), 32)
    assert not expert_kernel_fits(_shapes(
        16, 128, 256, w_down=jax.ShapeDtypeStruct((16, 256, 128),
                                                  jnp.float32)), 32)
    assert expert_path(small, 512, "compiled") \
        == expert_path(small, 512, "interpret") == "kernel"
    assert expert_path(small, 512, None) == "loop"
    assert expert_path(small, 4096, "compiled") == "loop"
    assert expert_path(_shapes(16, 64, 128), 32, "compiled") == "loop"


def test_off_the_tpu_the_program_runs_the_loop():
    """No backend for a kernel here (``_kernel_mode()`` is None off the
    TPU): the layer's program holds no kernel call, with the widths that
    would fit one too."""
    assert paged._kernel_mode() is None
    moe, law = _layer("softmax_128")
    text = jax.jit(lambda y: routed_experts_apply(
        moe, y, kernel_mode=paged._kernel_mode(), **law)).lower(
            _rows(32)).as_text()
    assert "while" in text and "custom_call" not in text


# -- a routed engine names the path on its launch spans ----------------------

WIDE = dict(d_model=128, expert_d_ff=128)  # widths the kernel reads
KIND = "latent_moe"


def _engine(guard=None, **changes):
    return ServingEngine(
        params_of(KIND, 5, jnp.float32, **changes),
        config_of(KIND, jnp.float32, **changes),
        EngineConfig(num_slots=3, block_size=4, num_blocks=64,
                     max_request_len=64, prefill_chunk=8), guard=guard)


def _streams(engine):
    rng = np.random.default_rng(31)
    vocab = KINDS[KIND].tc["vocab_size"]
    for rid, prompt, new in (("long", 21, 5), ("s0", 5, 6), ("s1", 13, 4)):
        engine.submit(Request(rid, rng.integers(0, vocab, prompt), new))
    return {rid: r.tokens for rid, r in engine.run().items()}


def _launches(since):
    me = threading.current_thread().name
    return [r[4] for r in profiling.spans(
        since=since, name="kubeshare.engine.launch") if r[3] == me]


def test_a_routed_engines_launches_say_what_runs_the_tiles(monkeypatch):
    """``experts`` = ``kernel`` where a kernel can run and the experts'
    widths fit it, ``loop`` otherwise (off the TPU; on it, the twin's
    own 64-wide experts); the streams and the routing counts are the
    loop's, and nothing compiles after warm-up."""
    since = time.monotonic()
    loop = _engine(**WIDE)
    want = _streams(loop)
    assert {a["experts"] for a in _launches(since)} == {"loop"}
    monkeypatch.setattr(paged, "_kernel_mode", lambda: "interpret")
    since = time.monotonic()
    _streams(_engine())  # d_model 64: the shapes do not fit
    assert {a["experts"] for a in _launches(since)} == {"loop"}
    engine = _engine(**WIDE)
    engine.warmup()
    baseline = engine.compile_counts()
    since = time.monotonic()
    assert _streams(engine) == want
    assert engine.compile_counts() == baseline
    assert {a["experts"] for a in _launches(since)} == {"kernel"}
    for counter in ("moe_assignments", "moe_experts_touched", "moe_passes",
                    "moe_tiles", "moe_tile_rows"):
        assert getattr(engine, counter) == getattr(loop, counter), counter


def test_a_dense_engines_launches_have_no_experts(monkeypatch):
    from kubeshare_tpu.models.transformer import (TransformerConfig,
                                                  transformer_init)

    config = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq_len=64, dtype=jnp.float32, attention="reference",
        positional="rope")
    for mode in (None, "interpret"):
        monkeypatch.setattr(paged, "_kernel_mode", lambda: mode)
        engine = ServingEngine(
            transformer_init(jax.random.PRNGKey(0), config), config,
            EngineConfig(num_slots=2, block_size=8, num_blocks=9,
                         max_request_len=32, prefill_chunk=8))
        since = time.monotonic()
        engine.submit(Request("r", np.arange(1, 7, dtype=np.int32), 3))
        engine.run()
        launches = _launches(since)
        assert launches and all("experts" not in a for a in launches)


def test_a_slow_routed_dispatch_names_the_path(monkeypatch):
    """The slow-dispatch WARNING of a gated routed engine prints what ran
    the tiles beside the program (a dense engine's: ``experts=none``,
    ``tests/test_tracing.py``)."""
    from test_tracing import FakeTokenClient

    engine = _engine(ExecutionGuard(client=FakeTokenClient(), from_env=False,
                                    idle_release_ms=0))
    engine.warmup()
    engine.submit(Request("warm", np.arange(1, 10, dtype=np.int32), 3))
    engine.run()
    lines = []
    handler = logging.Handler()
    handler.emit = lines.append
    engine.log.addHandler(handler)
    monkeypatch.setattr(engine_module, "SLOW_DISPATCH_S", 0.05)
    fast = engine._prefill_step
    slow_for = max(0.2, 10 * engine._dispatch_estimate_ms / 1e3)

    def slow(*args, **kwargs):
        time.sleep(slow_for)
        return fast(*args, **kwargs)

    try:
        engine.submit(Request("r", np.arange(2, 12, dtype=np.int32), 3))
        engine._prefill_step = slow
        engine.step()
        engine._prefill_step = fast
        engine.run()
    finally:
        engine.log.removeHandler(handler)
    warnings = [r.getMessage() for r in lines
                if r.levelno == logging.WARNING]
    assert len(warnings) == 1
    assert "program=prefill/8" in warnings[0]
    assert "experts=loop" in warnings[0]
