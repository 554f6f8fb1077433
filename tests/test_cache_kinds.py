"""Attention KINDS a layer may name beside "attention" — "global" (every
earlier row, nothing rotated) and "window" (``attention_window`` rows back,
rotated) — with a router that reads the layer's input and ReLU-gated experts,
served from a cache BY LAYER KIND (a pool and a table a kind: the step
programs take the kinds' arrays as tuples and a lane's tables side by side),
held to the plain reference (``chipbench/smallthinker_21ba3b_reference.py``:
every row against every earlier row under the layer's own mask, no cache, no
page) at a small size on the CPU: d 64, 14 query heads on 2 KV heads of width
16 (a query group of 7), 8 experts top 3, a window of 20 rows over pages of 8,
seeded weights.  The engine's side is ``test_cache_kinds_engine.py``.
"""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import smallthinker_21ba3b_reference as reference  # noqa: E402
from chipbench import smallthinker_21ba3b_roofline as counts  # noqa: E402
from chipbench import smallthinker_21ba3b_weights as weights  # noqa: E402
from kubeshare_tpu.models.decoding import _attend_blocks  # noqa: E402
from kubeshare_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, transformer_apply, transformer_init)
from kubeshare_tpu.ops.moe import route, routed_experts_apply  # noqa: E402
from kubeshare_tpu.ops.paged_attention import paged_decode_attention  # noqa: E402
from kubeshare_tpu.serving import paged  # noqa: E402
from kubeshare_tpu.serving.kv_blocks import (  # noqa: E402
    KVRowLayout, init_conv_states, init_paged_pool, kind_blocks,
    kv_row_layout, require_kv_heads, window_reserve_rows)

WINDOW = 20  # its edge lies off a page's (pages of 8 rows)
OPERATORS = ["global", "window", "window", "window", "global", "window"]
TC = {"vocab_size": 512, "d_model": 64, "n_heads": 14, "n_kv_heads": 2,
      "n_layers": 6, "d_ff": 0, "max_seq_len": 1024, "positional": "rope",
      "dtype": "float32", "block": "gqa_moe", "head_width": 16,
      "rope_theta": 1500000.0, "norm_eps": 1e-06, "n_routed_experts": 8,
      "router_top_k": 3, "routed_scaling_factor": 1.0,
      "router_scoring": "softmax", "router_renormalise": True,
      "expert_d_ff": 32, "layer_operators": OPERATORS,
      "attention_window": WINDOW, "qk_norm": False,
      "router_input": "layer_input", "expert_activation": "relu"}
# float32 end to end: the program and the reference differ by the order of
# their sums (the softmax's key blocks, the experts' tiles) and by `highest`
# against the CPU's default products: 2e-6 on logits of size 3 here
LOGIT_TOLERANCE = 3e-5


def _config(**changes) -> TransformerConfig:
    tc = {**TC, **changes}
    return TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})


@pytest.fixture(autouse=True)
def short_references(monkeypatch):
    monkeypatch.setattr(reference, "PAD_TO", 64)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)


@pytest.fixture(scope="module")
def model():
    return TC, _config(), weights.make_weights(11, TC)


@pytest.fixture(scope="module", autouse=True)
def executables_let_go():
    yield
    jax.clear_caches()


def _prompt(seed: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 500, length).astype(
        np.int32)


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------

def test_the_block_has_the_published_shape(model):
    tc, config, params = model
    assert (config.attn_sublayers, config.window_layers,
            config.conv_layers) == (6, 4, 0)
    # a layer's place among the layers of ITS pool
    assert [config.operator_index(i) for i in range(6)] == [
        ("global", 0), ("window", 0), ("window", 1), ("window", 2),
        ("global", 1), ("window", 3)]
    layout = kv_row_layout(config)
    assert layout.kind_layers == (2, 4) and layout.k_row == (2, 16)
    mine = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), config))
    assert jax.tree.map(lambda a: a.shape, mine) \
        == jax.tree.map(lambda a: a.shape, params)
    assert set(params["layers"][0]["attn"]) == {"wq", "wk", "wv", "wo"}
    assert counts.kv_bytes_per_row(tc) == 6 * 2 * 2 * 16 * 4
    assert counts.kv_read_bytes_by_kind(tc) == {"full": 2 * 256,
                                                "window": 4 * 256}


@pytest.mark.parametrize("changes,said", [
    (dict(attention_window=None), "attention_window must be >= 1"),
    (dict(layer_operators=["global"] * 6), "nor attention_window"),
    (dict(layer_operators=["window"] * 6), "no 'attention' or 'global'"),
    (dict(layer_operators=["global", "window", "conv", "window", "global",
                           "window"], conv_taps=3), "'window' and 'conv'"),
    (dict(layer_operators=None, attention_window=None),
     "qk_norm=False is served where the layers name"),
    (dict(layer_operators=None, qk_norm=True), "attention_window"),
    (dict(router_input="norm1"), "router_input must be"),
    (dict(expert_activation="gelu"), "expert_activation 'silu' or 'relu'"),
    (dict(layer_operators=None, attention_window=None, qk_norm=True,
          diffusion_block=4, diffusion_steps=4, mask_token=1,
          router_input="layer_input"), None),
])
def test_a_configuration_that_makes_no_sense_is_refused(changes, said):
    if said is None:  # the switches alone are the block's, whatever it serves
        assert _config(**changes).router_input == "layer_input"
        return
    with pytest.raises(ValueError, match=said):
        _config(**changes)


def test_a_window_under_diffusion_and_in_other_blocks_stays_refused():
    with pytest.raises(ValueError, match="causal mask only"):
        _config(diffusion_block=4, diffusion_steps=4, mask_token=1)
    with pytest.raises(ValueError, match="block 'gqa_moe''s"):
        TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                          d_ff=32, max_seq_len=64, qk_norm=False)


def test_the_unpaged_forward_is_the_reference(model):
    tc, config, params = model
    tokens = _prompt(3, 150)
    mine = np.asarray(transformer_apply(params, jnp.asarray(tokens[None]),
                                        config))[0]
    rows = np.arange(5, 150)
    theirs = reference.reference_logits(params, tc, tokens, rows)
    assert np.abs(mine[rows] - theirs).max() < LOGIT_TOLERANCE
    # the three switches and both kinds matter: a model without any one of
    # them is another model
    for other in (dict(router_input="post_attention"),
                  dict(expert_activation="silu"),
                  dict(attention_window=1000),
                  dict(layer_operators=["attention" if o == "global" else o
                                        for o in OPERATORS])):
        theirs_not = np.asarray(transformer_apply(
            params, jnp.asarray(tokens[None]), _config(**other)))[0]
        assert np.abs(theirs_not[rows] - theirs).max() > 100 \
            * LOGIT_TOLERANCE, other


# ---------------------------------------------------------------------------
# the step programs over a pool by kind: prefill in chunks, then decode
# ---------------------------------------------------------------------------

POISON = 7.0  # finite: a masked key's weight is an exact 0, times anything


def _served_logits(config, params, tokens, prompt_len, chunk=16, bs=8):
    """Logits of every chunk's last row and of every decode step through the
    paged programs over a pool by kind, lane 1 of 3, as the engine drives
    them: the window kind's pages wholly behind the next dispatch's window
    are handed back before it (their table entries point at the scratch
    block, the pages themselves are POISONED) and the pages ahead drawn."""
    n = len(tokens)
    pages = -(-n // bs)
    pool = init_paged_pool(config, 2, bs, kinds=(2 + pages, 2 + pages))
    pk, pv = pool.k, pool.v
    width = 1024 // bs
    table = np.zeros((2 * width,), np.int32)
    table[:pages] = np.arange(1, pages + 1)  # the full kind: every page
    near = list(range(pages, 0, -1))  # the window kind draws from here
    live = {}  # entry -> page

    def hand_over(rows, adds):
        """Before a dispatch that starts at row ``rows`` and adds ``adds``."""
        nonlocal pk, pv
        first = max(rows - WINDOW + 1, 0) // bs
        for entry in [e for e in live if e < first]:
            page = live.pop(entry)
            table[width + entry] = 0
            pk = (pk[0], pk[1].at[:, page].set(POISON))
            pv = (pv[0], pv[1].at[:, page].set(POISON))
            near.append(page)
        for entry in range(first, (rows + adds - 1) // bs + 1):
            if entry not in live:
                live[entry] = near.pop()
                table[width + entry] = live[entry]

    got, at = {}, 0
    while at < prompt_len:
        rows = min(chunk, prompt_len - at)
        hand_over(at, chunk)
        segment = np.zeros((1, chunk), np.int32)
        segment[0, :rows] = tokens[at:at + rows]
        logits, pk, pv, _ = jax.jit(
            lambda pk, pv, table, seg, start, last: paged.paged_prefill_step(
                params, config, pk, pv, table, start, jnp.ones((1,), bool),
                seg, last, routing=True))(
            pk, pv, jnp.asarray(table[None]), jnp.asarray(segment),
            jnp.asarray([at]), jnp.asarray([rows - 1]))
        at += rows
        got[at - 1] = np.asarray(logits[0])
    active = jnp.asarray([False, True, False])
    step = jax.jit(lambda pk, pv, tables, lens, toks: paged.paged_decode_step(
        params, config, pk, pv, tables, lens, active, toks, routing=True))
    for at in range(prompt_len, n):
        hand_over(at, 1)
        tables = np.zeros((3, 2 * width), np.int32)
        tables[1] = table
        lane = lambda value: jnp.zeros((3,), jnp.int32).at[1].set(value)
        logits, pk, pv, _ = step(pk, pv, jnp.asarray(tables), lane(at),
                                 lane(int(tokens[at])))
        got[at] = np.asarray(logits[1])
        assert len(live) <= window_reserve_rows(WINDOW, bs, chunk) // bs
    return got


def test_prefill_in_chunks_then_decode_gives_the_references_logits(model):
    """A prompt of 100 rows in chunks of 16 (the last one 4 real rows and 12
    of padding), then 60 decode steps, in float32: the request crosses the
    20-row window seven times, the window's edge moves through the pages
    of 8, and every released page is poisoned — every logit the programs
    give is the full forward's."""
    tc, config, params = model
    tokens = _prompt(4, 160)
    got = _served_logits(config, params, tokens, 100)
    assert len(got) == 7 + 60
    rows = np.asarray(sorted(got))
    theirs = reference.reference_logits(params, tc, tokens, rows)
    worst = max(np.abs(got[r] - theirs[i]).max()
                for i, r in enumerate(rows))
    assert worst < LOGIT_TOLERANCE, worst


def test_a_mixed_dispatch_over_both_kinds_gives_the_references_logits(
        model, monkeypatch):
    """The fused mixed step over a pool by kind (a chunk's rows and the
    lanes' rows through one layer loop, each group attending each kind's
    table), with the key block patched to 16 rows so that the chunk's
    window starts blocks in: the chunk's last row and a decode lane's rows
    are the full forward's."""
    monkeypatch.setattr(paged, "KEY_BLOCK", 16)
    tc, config, params = model
    bs, width = 8, 1024 // 8
    lane_tokens, chunk_tokens = _prompt(5, 90), _prompt(6, 64)
    pool = init_paged_pool(config, 2, bs, kinds=(40, 40))
    pk, pv = pool.k, pool.v
    # a lane's pages: entries 0-11 of each kind's table (the kinds' pools
    # are apart: the same ids are other pages)
    table = lambda first: np.tile(
        np.pad(np.arange(first, first + 12), (0, width - 12)), 2
    ).astype(np.int32)

    def prefill(pk, pv, lane_table, tokens, rows):
        for at in range(0, rows, 16):
            _, pk, pv = paged.paged_prefill_step(
                params, config, pk, pv, jnp.asarray(lane_table[None]),
                jnp.asarray([at]), jnp.ones((1,), bool),
                jnp.asarray(tokens[None, at:at + 16]), jnp.asarray([15]))
        return pk, pv

    # the decode lane's context of 80 rows, the chunk's lane's first 48
    d_table, p_table = table(1), table(20)
    pk, pv = prefill(pk, pv, d_table, lane_tokens, 80)
    pk, pv = prefill(pk, pv, p_table, chunk_tokens, 48)
    # entries wholly behind either window go back: the chunk's queries
    # start at 48 (rows from 29 on: entry 3), the lane's at 80 (61: entry 7)
    p_table[width:width + 3] = 0
    d_table[width:width + 7] = 0
    tables = np.zeros((2, 2 * width), np.int32)
    tables[1] = d_table
    logits, pk, pv, *_ = paged._mixed_first_step(
        params, config, pk, pv, jnp.asarray(p_table[None]),
        jnp.asarray([48]), jnp.asarray(chunk_tokens[None, 48:64]),
        jnp.asarray([15]), jnp.asarray(tables), jnp.asarray([0, 80]),
        jnp.asarray([False, True]), jnp.asarray([0, lane_tokens[80]]))
    theirs = reference.reference_logits(params, tc, lane_tokens[:81],
                                        np.asarray([80]))[0]
    assert np.abs(np.asarray(logits[1]) - theirs).max() < LOGIT_TOLERANCE
    theirs = reference.reference_logits(params, tc, chunk_tokens,
                                        np.asarray([63]))[0]
    assert np.abs(np.asarray(logits[2]) - theirs).max() < LOGIT_TOLERANCE


# ---------------------------------------------------------------------------
# the attention a layer at a time: the kernel and the loop under a window
# ---------------------------------------------------------------------------

def _lanes(seed: int, window: int, bs: int = 16, pages: int = 32,
           lanes=(400, 0, 137, 511, 19)):
    """A pool of one layer pair at head width 128, 14 query heads on 2 KV
    heads (a query group of 7), and lanes at the given positions (0: idle)
    whose table entries wholly behind their window point at the scratch
    block, as the engine leaves them."""
    rng = np.random.default_rng(seed)
    blocks = 1 + len(lanes) * pages
    shape = (2, blocks, 2, bs, 128)
    pool_k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    tables = np.zeros((len(lanes), pages), np.int32)
    for i, position in enumerate(lanes):
        if position:
            tables[i] = 1 + i * pages + np.arange(pages)
            tables[i, :max(position - window + 1, 0) // bs] = 0
            tables[i, position // bs + 1:] = 0
    q = jnp.asarray(rng.normal(size=(len(lanes), 14, 1, 128)), jnp.float32)
    return q, pool_k, pool_v, jnp.asarray(tables), \
        jnp.asarray(lanes, jnp.int32)[:, None]


@pytest.mark.parametrize("window", [64, 100, 1000])
def test_the_kernel_under_a_window_is_the_key_block_loop(window):
    """``paged_decode_attention`` (interpreted) with ``window`` against the
    key-block loop with the same window, a query group of 7 rows a KV head
    (padded to a tile inside the call): the walk starts at the page the
    window starts in, a lane whose first entries were handed back is live,
    an idle lane reads zeros."""
    q, pool_k, pool_v, tables, positions = _lanes(window, window)
    live = np.asarray(positions[:, 0]) > 0
    for layer in (0, 1):
        kernel = paged_decode_attention(
            q[:, :, 0], pool_k, pool_v, layer, tables, positions[:, 0],
            window=window, interpret=True)
        loop = paged._attend_view_blocks(
            q, pool_k, pool_v, layer, tables, positions,
            paged.key_block_entries(tables.shape[1], 16), window)[:, :, 0]
        assert np.abs(np.asarray(kernel - loop))[live].max() < 1e-5
        assert not np.asarray(kernel)[~live].any()


def test_attend_view_takes_the_kernel_for_one_row_a_lane(monkeypatch):
    monkeypatch.setattr(paged, "_kernel_mode", lambda: "interpret")
    monkeypatch.setattr(paged, "KEY_BLOCK", 128)
    q, pool_k, pool_v, tables, positions = _lanes(1, 64)
    assert paged.attend_path("gqa_moe", 1, tables.shape[1], pool_k, pool_v,
                             128) == "kernel"
    kernel = paged._attend_view(q, pool_k, pool_v, 1, tables, positions, 64)
    monkeypatch.setattr(paged, "_kernel_mode", lambda: None)
    assert paged.attend_path("gqa_moe", 1, tables.shape[1], pool_k, pool_v,
                             128) == "blocks"
    loop = paged._attend_view(q, pool_k, pool_v, 1, tables, positions, 64)
    live = np.asarray(positions[:, 0]) > 0
    assert np.abs(np.asarray(kernel - loop))[live].max() < 1e-5


@pytest.mark.parametrize("window", [40, 64, 200])
def test_the_loop_from_the_windows_first_block_is_the_loop_from_block_0(
        window):
    """A chunk's 32 queries from row 352 on: alone, the loop starts at the
    block that holds row ``352 - window + 1``; beside a lane at row 0 it
    starts at block 0 (the step's earliest query is there).  The chunk's
    numbers are the same to the bit: a block no query of a lane sees is an
    exact no-op for it."""
    rng = np.random.default_rng(window)
    _, pool_k, pool_v, tables, _ = _lanes(3, window, lanes=(383, 40))
    q = jnp.asarray(rng.normal(size=(2, 14, 32, 128)), jnp.float32)
    positions = jnp.asarray([352, 0])[:, None] + jnp.arange(32)[None, :]
    entries = 2  # key blocks of 32 rows

    def blocks_walked(rows):
        first = max(int(rows.min()) - window + 1, 0) // 32
        return int(rows.max()) // 32 + 1 - first

    assert blocks_walked(np.asarray(positions[:1])) \
        == 12 - (352 - window + 1) // 32 < 12
    assert blocks_walked(np.asarray(positions)) == 12
    alone = paged._attend_view_blocks(
        q[:1], pool_k, pool_v, 0, tables[:1], positions[:1], entries, window)
    beside = paged._attend_view_blocks(
        q, pool_k, pool_v, 0, tables, positions, entries, window)
    assert np.array_equal(np.asarray(alone[0]), np.asarray(beside[0]))
    # and both are the window's own numbers: every key against the mask
    view_k, view_v = paged._layer_views(pool_k, pool_v, 0, tables[:1])
    whole = paged._attend_cached(q[:1], view_k, view_v, positions[:1],
                                 window=window)
    assert np.abs(np.asarray(alone - whole)).max() < 1e-5
    # without a window the trip count is what it was
    full = _attend_blocks(
        q[:1], lambda i: paged._layer_views(
            pool_k, pool_v, 0, jax.lax.dynamic_slice_in_dim(
                tables[:1], i * entries, entries, axis=1)), 32, 2,
        positions[:1])
    everything = paged._attend_cached(q[:1], view_k, view_v, positions[:1])
    assert np.abs(np.asarray(full - everything)).max() < 1e-5


# ---------------------------------------------------------------------------
# the router on the layer's input and the ReLU gate, kernel and loop alike
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel_mode", [None, "interpret"])
def test_early_router_and_relu_experts_are_the_references(kernel_mode):
    """One expert layer at d 128 / f 128 (what the grouped kernel takes):
    the choices made from OTHER rows than the experts read (the layer's
    input), softmax over the chosen three, ReLU on the gate — the
    ``fori_loop`` and the kernel (interpreted) against the reference's
    every-expert-over-every-row."""
    rng = np.random.default_rng(8)
    d, f, experts, top_k, n = 128, 128, 8, 3, 24
    make = lambda *shape: jnp.asarray(
        rng.normal(size=shape) / shape[-2] ** 0.5, jnp.float32)
    moe = {"router": make(d, experts), "w_gate": make(experts, d, f),
           "w_up": make(experts, d, f), "w_down": make(experts, f, d)}
    x_in, h = make(n, d) * 8, make(n, d) * 8
    norm = {"scale": jnp.ones((d,), jnp.float32)}
    y = reference._rms_norm(h, norm["scale"], 1e-6)
    law = dict(top_k=top_k, scale=1.0, scoring="softmax", renormalise=True)
    out, stats = routed_experts_apply(
        moe, y, n_routed=experts, **law, kernel_mode=kernel_mode,
        choices=route(moe, x_in, **law), activation="relu")
    theirs = reference._experts(
        h, reference.router_weights(x_in, moe["router"], top_k), norm, moe,
        1e-6) - h
    assert np.abs(np.asarray(out - theirs)).max() < 1e-5
    assert int(stats[0]) == n * top_k
    # the router's own rows and SiLU are other numbers
    late, _ = routed_experts_apply(moe, y, n_routed=experts, **law,
                                   kernel_mode=kernel_mode,
                                   activation="relu")
    silu, _ = routed_experts_apply(moe, y, n_routed=experts, **law,
                                   kernel_mode=kernel_mode,
                                   choices=route(moe, x_in, **law))
    assert np.abs(np.asarray(late - theirs)).max() > 1e-2
    assert np.abs(np.asarray(silu - theirs)).max() > 1e-2


# ---------------------------------------------------------------------------
# the pool's bytes add up
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_blocks", [16385, 4097, 1000, 129, 37, 9, 3])
def test_the_pools_bytes_are_num_blocks_of_every_layers_row(num_blocks):
    """``kind_blocks`` divides ``num_blocks`` blocks of every layer's row
    between the kinds with no byte left over, whatever the count, and both
    kinds fund the same number of worst-case lanes as near as whole blocks
    allow."""
    tc = {**TC, "n_layers": 8, "layer_operators": (OPERATORS + OPERATORS)[:8],
          "attention_window": 4096, "dtype": "bfloat16", "head_width": 128,
          "n_heads": 28, "n_kv_heads": 4}
    config = TransformerConfig(**{**tc, "dtype": jnp.bfloat16})
    layout = kv_row_layout(config)
    g, w = layout.kind_layers
    assert (g, w) == (3, 5)
    kinds = kind_blocks(layout, num_blocks, 16, 16384, 512, 4096)
    assert g * kinds[0] + w * kinds[1] == (g + w) * num_blocks
    assert min(kinds) >= 2
    pool_k, pool_v = jax.eval_shape(lambda: (lambda p: (p.k, p.v))(
        init_paged_pool(config, num_blocks, 16, kinds=kinds)))
    held = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves((pool_k, pool_v)))
    assert held == num_blocks * counts.kv_bytes_per_row(tc) * 16
    assert [a.shape for a in pool_k] == [
        (g, kinds[0], 4, 16, 128), (w, kinds[1], 4, 16, 128)]
    if num_blocks > 1000:
        reserve = window_reserve_rows(4096, 16, 512) // 16
        assert reserve == 289
        lanes = [(kinds[0] - 1) / 1024, (kinds[1] - 1) / reserve]
        assert abs(lanes[0] - lanes[1]) < 0.05 * lanes[0]
        # one table a lane funds (num_blocks - 1) / 1024 of them
        assert lanes[0] > 1.7 * (num_blocks - 1) / 1024


def test_the_cells_pool_is_divided_as_its_file_says():
    layout = KVRowLayout("kv_heads", 8, (4, 128), (4, 128), window_layers=6)
    assert kind_blocks(layout, 16385, 16, 16384, 512, 4096) == (35492, 10016)
    with pytest.raises(ValueError, match="cannot be divided"):
        kind_blocks(layout, 1, 16, 16384, 512, 4096)
    # no engine to say what it serves: still every byte, divided for
    # requests of max_seq_len rows
    pool = jax.eval_shape(lambda: (lambda p: (p.k, p.v))(
        init_paged_pool(_config(), 40, 8)))
    assert sum(a.size for a in jax.tree.leaves(pool)) \
        == 40 * 8 * 6 * 2 * 2 * 16
    with pytest.raises(ValueError, match="caches BY LAYER KIND"):
        require_kv_heads(_config(), "ReplicaFleet's shared host tier")


# ---------------------------------------------------------------------------
# a configuration that names none of the new values lowers what it lowered
# ---------------------------------------------------------------------------

_BASE = dict(vocab_size=512, d_model=64, n_heads=4, n_kv_heads=2, n_layers=4,
             d_ff=96, max_seq_len=1024, positional="rope", dtype=jnp.float32,
             block="gqa_moe", head_width=64, rope_theta=1e6, norm_eps=1e-5,
             n_routed_experts=8, router_top_k=2, router_renormalise=True,
             expert_d_ff=32)
# sha256 of the lowered text (``jax.jit(fn).lower(...).as_text()``) of the
# mixed program of each, by the PARENT of the PR that brought the kinds
# (c2c52be, this installation: jax 0.9.0).  The "lfm2" one was re-pinned by
# PR 48 (on 719ea7b, where it still read d12cf1ad...9ad7002), which MEANT to
# change that program: a state by slot rides the fused first step, and the
# kinds' branches are as dead in the new text as they were in the old
LIKE = {
    "sdar": (dict(_BASE, diffusion_block=4, diffusion_steps=4,
                  mask_token=511),
             "2e6acbfc2cf65ed66d96cab220882584241cd939690d4f22f6305155594db02f"),
    "lfm2": (dict(_BASE, router_scoring="sigmoid", router_choice_bias=True,
                  router_renormalise_eps=1e-6, first_dense_layers=1,
                  layer_operators=("conv", "conv", "attention", "conv"),
                  conv_taps=3),
             "53599c068e5c5d1af74ef589c9f902e940ffb3624ffabd6866d63764c8f43e77"),
}


@pytest.mark.parametrize("name", sorted(LIKE))
def test_a_model_without_the_new_values_lowers_the_same_text(name):
    """Every new branch is static and dead for a configuration that names
    no kind and none of the three switches: its mixed program's lowered
    text is the parent's, character for character."""
    fields, parents = LIKE[name]
    config = TransformerConfig(**fields)
    params = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), config))
    s, t, bs, span, chunk = 4, 64, 16, 4, 32
    pool_k, pool_v = jax.eval_shape(
        lambda: init_paged_pool(config, 33, bs).arrays())
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    u32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32)
    flag = lambda *shape: jax.ShapeDtypeStruct(shape, bool)
    pick = lambda logits, temps, keys: jnp.argmax(logits, -1).astype(
        jnp.int32)
    if name == "sdar":
        b = config.diffusion_block
        fn = lambda w, pk, pv, *rest: paged.paged_mixed_diffusion_step(
            w, config, pk, pv, *rest, routing=True)
        args = (params, pool_k, pool_v, i32(1, t), i32(1), i32(1, chunk),
                i32(1), i32(s, t), i32(s), flag(s), i32(s, b), flag(s, b),
                flag(s, b), i32(s))
    else:
        rec = paged.Recurrent(None, jax.eval_shape(
            lambda: init_conv_states(config, s)))
        fn = lambda w, pk, pv, rec, p_folded, p_slot, d_folded, *rest: \
            paged.paged_mixed_step(
                w, config, pick, span, None, pk, pv, *rest, routing=True,
                recurrent=rec, p_folded=p_folded, p_slot=p_slot,
                d_folded=d_folded)
        args = (params, pool_k, pool_v, rec, i32(1), i32(1), i32(s),
                i32(1, t), i32(1), i32(1, chunk), i32(1), f32(1), u32(1, 2),
                i32(s, t), i32(s), flag(s), i32(s), f32(s),
                u32(s, span, 2), i32(s))
    text = jax.jit(fn).lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == parents
