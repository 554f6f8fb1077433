"""Layers that name their operator (``TransformerConfig.layer_operators``):
gated short convolutions (``ops/short_conv.py``) whose state — the last
``conv_taps - 1`` rows of ``B * u`` a lane, BY SLOT beside the paged pool —
is carried through every step program, beside attention layers whose 64-wide
KV heads lie two to a pool row, held to the plain reference
(``chipbench/lfm2_24b_a2b_reference.py``: every row against every earlier
row, no state) at a small size on the CPU: d 64, 4 query heads on 2 KV heads
of width 64, 8 experts top 2 behind a leading dense layer, seeded weights.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import lfm2_24b_a2b_reference as reference  # noqa: E402
from chipbench import lfm2_24b_a2b_roofline as counts  # noqa: E402
from chipbench import lfm2_24b_a2b_weights as weights  # noqa: E402
from chipbench import sdar_30b_a3b_chat_reference as gqa_reference  # noqa: E402
from kubeshare_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, transformer_apply, transformer_init)
from kubeshare_tpu.ops.short_conv import short_conv, state_after  # noqa: E402
from kubeshare_tpu.parallel.mesh import MeshSpec  # noqa: E402
from kubeshare_tpu.serving import (  # noqa: E402
    QOS_OPPORTUNISTIC, EngineConfig, Request, ServingEngine, TenantRegistry,
    TenantSpec, paged, stages)
from kubeshare_tpu.serving.kv_blocks import (  # noqa: E402
    init_conv_states, init_paged_pool, kv_row_layout)
from kubeshare_tpu.utils import profiling  # noqa: E402

OPERATORS = ["conv", "conv", "attention", "conv", "attention"]
TC = {"vocab_size": 512, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
      "n_layers": 5, "d_ff": 96, "max_seq_len": 1024, "positional": "rope",
      "dtype": "float32", "block": "gqa_moe", "head_width": 64,
      "rope_theta": 1000000.0, "norm_eps": 1e-05, "n_routed_experts": 8,
      "router_top_k": 2, "routed_scaling_factor": 1.0,
      "router_scoring": "sigmoid", "router_choice_bias": True,
      "router_renormalise": True, "router_renormalise_eps": 1e-06,
      "expert_d_ff": 32, "first_dense_layers": 1,
      "layer_operators": OPERATORS, "conv_taps": 3}
# float32 end to end: the program and the reference differ by the order of
# their sums (the filter's three products, the softmax's key blocks, the
# experts' tiles) and by `highest` against the CPU's default products, 2e-6
# on logits of size 3 here
LOGIT_TOLERANCE = 1e-5 * 3
# bf16 weights and rows: the program rounds every matrix product's inputs,
# g = B * u and the state that holds it to 8 bits of mantissa where the
# reference keeps 24; over five layers that moves a logit of size 3.5 by
# 0.06-0.10 in the MEDIAN row (measured here over three seeds), and the
# reference's own fp8 pass (3 bits) moves the median row by 1.0.  The WORST
# row is not held: a router choice near a tie (2 of 8 here) decided the
# other way swaps an expert and moves that row by 0.5-1.1 in sound runs
# (PERF.md section 7, item 12: a routed block cannot hold a widest gap)
BF16_MEDIAN_TOLERANCE = 0.2


def _config(**changes) -> TransformerConfig:
    tc = {**TC, **changes}
    return TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})


@pytest.fixture(autouse=True)
def short_references(monkeypatch):
    monkeypatch.setattr(reference, "PAD_TO", 64)
    monkeypatch.setattr(gqa_reference, "QUERY_BLOCK", 32)


@pytest.fixture(scope="module")
def model():
    return TC, _config(), weights.make_weights(11, TC)


@pytest.fixture(scope="module", autouse=True)
def executables_let_go():
    """This file compiles some two hundred programs (three engines' warm-ups
    among them).  A tier-1 worker keeps every file's executables loaded, each
    with memory maps of its own, and the kernel allows a process 65,530: the
    worker that ran this file after ``test_diffusion_blocks.py`` crossed it
    files later, inside another file's warm-up (a segmentation fault where
    XLA loads an executable).  So what was compiled here is let go."""
    yield
    jax.clear_caches()


def _engine(config, params, **changes) -> ServingEngine:
    kwargs = dict(num_slots=3, block_size=8, num_blocks=1 + 3 * 40,
                  max_request_len=320, prefill_chunk=32, decode_span=4)
    tenants = changes.pop("tenants", None)
    kwargs.update(changes)
    return ServingEngine(params, config, EngineConfig(**kwargs),
                         tenants=tenants)


def _prompt(seed: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 500, length).astype(
        np.int32)


def _gaps(params, tc, prompt, served) -> np.ndarray:
    return reference.served_gaps(params, tc, prompt, served)


# ---------------------------------------------------------------------------
# the operator
# ---------------------------------------------------------------------------

def _conv_weights(seed: int, d: int, taps: int = 3):
    rng = np.random.default_rng(seed)
    return {"w_in": jnp.asarray(rng.normal(size=(d, 3 * d)) / d ** 0.5,
                                jnp.float32),
            "filter": jnp.asarray(rng.normal(size=(taps, d)), jnp.float32),
            "w_out": jnp.asarray(rng.normal(size=(d, d)) / d ** 0.5,
                                 jnp.float32)}


@pytest.mark.parametrize("taps", [2, 3, 4])
def test_the_operator_is_the_tap_sum_written_out(taps):
    """``c_t = w[0] g_{t-2} + w[1] g_{t-1} + w[2] g_t`` at three taps, zeros
    before row 0, ``out = (C * c) W_out``: a loop over rows and taps in
    float64 against the shifted multiply-adds."""
    d, rows = 16, 11
    conv = _conv_weights(taps, d, taps)
    y = jnp.asarray(np.random.default_rng(1).normal(size=(2, rows, d)),
                    jnp.float32)
    out, window = short_conv(conv, y, jnp.zeros((2, taps - 1, d)),
                             jnp.float32)
    w_in, w, w_out = (np.asarray(conv[k], np.float64)
                      for k in ("w_in", "filter", "w_out"))
    bcu = np.asarray(y, np.float64) @ w_in
    b, c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    g = b * u
    mixed = np.zeros_like(g)
    for t in range(rows):
        for j in range(taps):
            back = taps - 1 - j  # tap j weighs the row `back` rows back
            if t - back >= 0:
                mixed[:, t] += w[j] * g[:, t - back]
    assert np.abs(np.asarray(out) - (c * mixed) @ w_out).max() < 1e-4
    assert window.shape == (2, taps - 1 + rows, d)
    assert np.abs(np.asarray(window[:, taps - 1:]) - g).max() < 1e-5


@pytest.mark.parametrize("cuts", [(5, 14, 15), (1, 2, 3, 30), (7,), (31,)])
def test_whole_chunked_and_step_by_step_agree(cuts):
    """A sequence of 32 rows at once, in chunks whose boundaries are no
    chunk multiples, and a row at a time, each chunk handed the state the one
    before left: the same numbers."""
    d, rows = 16, 32
    conv = _conv_weights(5, d)
    y = jnp.asarray(np.random.default_rng(2).normal(size=(1, rows, d)),
                    jnp.float32)
    zeros = jnp.zeros((1, 2, d))
    whole, _ = short_conv(conv, y, zeros, jnp.float32)

    def in_pieces(bounds):
        state, outs = zeros, []
        for lo, hi in zip((0,) + bounds, bounds + (rows,)):
            out, window = short_conv(conv, y[:, lo:hi], state, jnp.float32)
            state = state_after(window, jnp.asarray([hi - lo]), 3)
            outs.append(out)
        return jnp.concatenate(outs, axis=1)

    assert np.abs(np.asarray(in_pieces(cuts) - whole)).max() < 1e-5
    by_step = in_pieces(tuple(range(1, rows)))
    assert np.abs(np.asarray(by_step - whole)).max() < 1e-5


def test_the_state_is_of_the_last_live_rows_not_of_the_padding():
    """A chunk of 8 rows of which 5 are real leaves ``g`` of rows 3 and 4; a
    lane with one real row shifts by one; a lane with none keeps what it
    held, to the bit."""
    d = 16
    conv = _conv_weights(7, d)
    rng = np.random.default_rng(3)
    y = jnp.asarray(rng.normal(size=(3, 8, d)), jnp.float32)
    held = jnp.asarray(rng.normal(size=(3, 2, d)), jnp.float32)
    _, window = short_conv(conv, y, held, jnp.float32)
    after = state_after(window, jnp.asarray([5, 1, 0]), 3)
    g = window[:, 2:]
    assert np.array_equal(after[0], g[0, 3:5])
    assert np.array_equal(after[1], jnp.stack([held[1, 1], g[1, 0]]))
    assert np.array_equal(after[2], held[2])


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------

def test_the_block_has_the_published_shape(model):
    """The cell's configuration by shapes alone: the pattern, the parameter
    count of the stage, a pool of the attention layers' rows only with two
    64-wide heads a 128-lane row, and a state of two rows a lane a
    convolution."""
    import json

    with open(os.path.join(REPO, "chipbench", "configs",
                           "lfm2-24b-a2b.json")) as f:
        tc = json.load(f)["transformer_config"]
    config = TransformerConfig(**{**tc, "dtype": jnp.bfloat16})
    assert config.layer_operators == ("conv", "conv", "attention", "conv") * 2
    assert (config.conv_layers, config.attn_sublayers,
            config.expert_layers) == (6, 2, 6)
    assert [config.operator_index(i) for i in (0, 2, 3, 6, 7)] == [
        ("conv", 0), ("attention", 0), ("conv", 2), ("attention", 1),
        ("conv", 5)]
    params = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), config))
    count = sum(a.size for a in jax.tree.leaves(params))
    assert count == counts.parameter_count(tc) == 4_159_511_168
    assert set(params["layers"][0]) == {"norm1", "norm2", "conv", "ffn"}
    assert set(params["layers"][2]) == {"norm1", "norm2", "attn", "moe"}
    assert params["layers"][0]["conv"]["w_in"].shape == (2048, 6144)
    assert params["layers"][0]["conv"]["filter"].shape == (3, 2048)
    assert params["layers"][2]["attn"]["wq"].shape == (2048, 2048)
    assert params["layers"][2]["attn"]["wk"].shape == (2048, 512)
    assert params["layers"][2]["moe"]["bias"].shape == (64,)
    made = jax.eval_shape(lambda: weights.make_weights(5, tc))
    assert jax.tree.map(lambda a: a.shape, made) \
        == jax.tree.map(lambda a: a.shape, params)
    layout = kv_row_layout(config)
    assert (layout.kind, layout.layers, layout.k_row, layout.v_row,
            layout.heads_paired) == ("kv_heads", 2, (4, 128), (4, 128), 2)
    assert layout.values_per_row() * 2 == counts.kv_bytes_per_row(tc) == 4096
    pool = jax.eval_shape(lambda: init_paged_pool(config, 16385, 16).arrays())
    assert [a.shape for a in pool] == [(2, 16385, 4, 16, 128)] * 2
    states = jax.eval_shape(lambda: init_conv_states(config, 32))
    assert [s.shape for s in states] == [(32, 2, 2048)] * 6
    assert states[0].dtype == jnp.bfloat16
    assert counts.state_bytes_per_lane(tc) == 6 * 2 * 2048 * 2 == 49_152
    # no routed expert in the least a step must read; the states where the
    # caller says how many lanes
    assert counts.decode_step_min_bytes(tc, 100, 3) \
        == counts.decode_step_weight_bytes(tc) + 100 * 4096 + 3 * 2 * 49_152
    assert counts.decode_step_weight_bytes(tc) == 2 * (
        counts.outside_experts_count(tc) + 2048 + 2048 * 65536)
    assert counts.expert_bytes(tc) == 3 * 2048 * 1536 * 2


@pytest.mark.parametrize("changes,said", [
    (dict(layer_operators=OPERATORS[:4]), "one of .* a layer, 5 in all"),
    (dict(layer_operators=["conv", "linear"] * 2 + ["attention"]),
     "one of"),
    (dict(layer_operators=["conv"] * 5), "names no 'attention'"),
    (dict(conv_taps=0), "conv_taps must be >= 2"),
    (dict(conv_taps=1), "conv_taps must be >= 2"),
    (dict(layer_operators=["attention"] * 5), "and 0 where none is"),
    (dict(layer_operators=None), "means nothing without layer_operators"),
    (dict(diffusion_block=4, diffusion_steps=4), "causal mask only"),
    (dict(first_dense_layers=5), r"first_dense_layers must be in \[0, 5\)"),
    (dict(first_dense_layers=2, d_ff=0), "d_ff >= 1"),
    (dict(block="retention", router_choice_bias=False),
     "block 'retention' takes neither"),
    (dict(block="dense", head_width=None, rope_theta=10000.0,
          norm_eps=1e-6), "block 'dense' takes neither"),
])
def test_a_configuration_that_makes_no_sense_is_refused(changes, said):
    with pytest.raises(ValueError, match=said):
        _config(**changes)


def test_a_block_whose_layers_name_nothing_is_what_it_was():
    """Without ``layer_operators`` the 'gqa_moe' block keeps a row a head,
    a pool layer a model layer and projections a head: the diffusion cell's
    programs are untouched."""
    plain = {k: v for k, v in TC.items()
             if k not in ("layer_operators", "conv_taps")}
    config = TransformerConfig(**{**plain, "dtype": jnp.float32})
    layout = kv_row_layout(config)
    assert (layout.layers, layout.k_row, layout.heads_paired) \
        == (5, (2, 64), 1)
    assert config.operator_index(3) == ("attention", 3)
    params = jax.eval_shape(
        lambda: transformer_init(jax.random.PRNGKey(0), config))
    assert params["layers"][1]["attn"]["wq"].shape == (64, 4, 64)
    assert "ffn" in params["layers"][0] and "moe" in params["layers"][1]


def test_the_unpaged_forward_is_the_reference(model):
    tc, config, params = model
    tokens = _prompt(3, 150)
    mine = np.asarray(transformer_apply(params, jnp.asarray(tokens[None]),
                                        config))[0]
    rows = np.arange(5, 150)
    theirs = reference.reference_logits(params, tc, tokens, rows)
    assert np.abs(mine[rows] - theirs).max() < LOGIT_TOLERANCE


# ---------------------------------------------------------------------------
# the step programs: prefill in chunks, then decode, against the reference
# ---------------------------------------------------------------------------

def _served_logits(config, params, tokens, prompt_len, chunk=32, bs=8):
    """Logits of every chunk's last row and of every decode step, through
    the paged programs, a lane in slot 1 of 3 whose states hold another
    request's leavings; chunks pad forward where the prompt ends inside
    one.  Also the states at the end."""
    n = len(tokens)
    pool = init_paged_pool(config, 2 + -(-n // bs), bs)
    recurrent = paged.Recurrent(None, tuple(
        s + 7.0 for s in init_conv_states(config, 3)))
    pk, pv = pool.k, pool.v
    width = 1024 // bs
    table = np.zeros((width,), np.int32)
    table[: n // bs + 1] = np.arange(1, n // bs + 2)
    slot, at, got = 1, 0, {}
    prefill = jax.jit(lambda pk, pv, rec, seg, start, last:
                      paged.paged_prefill_step(
                          params, config, pk, pv, jnp.asarray(table[None]),
                          start, jnp.ones((1,), bool), seg, last,
                          routing=True, recurrent=rec,
                          folded=jnp.zeros((1,), jnp.int32),
                          slots=jnp.asarray([slot])))
    while at < prompt_len:
        rows = min(chunk, prompt_len - at)
        segment = np.zeros((1, chunk), np.int32)
        segment[0, :rows] = tokens[at:at + rows]
        logits, pk, pv, _, recurrent = prefill(
            pk, pv, recurrent, jnp.asarray(segment), jnp.asarray([at]),
            jnp.asarray([rows - 1]))
        at += rows
        got[at - 1] = np.asarray(logits[0])
    tables = np.zeros((3, width), np.int32)
    tables[slot] = table
    active = jnp.asarray([False, True, False])
    step = jax.jit(lambda pk, pv, rec, lens, toks:
                   paged.paged_decode_step(
                       params, config, pk, pv, jnp.asarray(tables), lens,
                       active, toks, routing=True, recurrent=rec))
    for at in range(prompt_len, n):
        lane = lambda value: jnp.zeros((3,), jnp.int32).at[slot].set(value)
        logits, pk, pv, _, recurrent = step(pk, pv, recurrent, lane(at),
                                            lane(int(tokens[at])))
        got[at] = np.asarray(logits[slot])
    return got, recurrent.states


def test_prefill_in_chunks_then_decode_gives_the_references_logits(model):
    """A prompt of 100 rows in chunks of 32 (the last one 4 real rows and
    28 of padding), then 50 decode steps, in float32: every logit the
    programs give is the full forward's, whatever the slot held before; and
    the idle lanes' states are untouched to the bit."""
    tc, config, params = model
    tokens = _prompt(4, 150)
    got, states = _served_logits(config, params, tokens, 100)
    assert len(got) == 4 + 50
    rows = np.asarray(sorted(got))
    theirs = reference.reference_logits(params, tc, tokens, rows)
    worst = max(np.abs(got[r] - theirs[i]).max()
                for i, r in enumerate(rows))
    assert worst < LOGIT_TOLERANCE, worst
    assert len(states) == 3
    for state in states:
        assert np.all(np.asarray(state[0]) == 7.0)
        assert np.all(np.asarray(state[2]) == 7.0)
        assert not np.any(np.asarray(state[1]) == 7.0)


def test_served_in_bf16_the_logits_stay_within_the_stated_tolerance():
    """The same in the precision the cell states: bf16 weights, rows and
    states, the filter's sum and the softmax in float32."""
    tc = {**TC, "dtype": "bfloat16"}
    config, params = _config(dtype="bfloat16"), weights.make_weights(12, tc)
    assert params["embed"].dtype == jnp.bfloat16
    tokens = _prompt(6, 120)
    got, states = _served_logits(config, params, tokens, 90)
    assert states[0].dtype == jnp.bfloat16
    rows = np.asarray(sorted(got))
    theirs = reference.reference_logits(params, tc, tokens, rows)
    off = np.median([np.abs(got[r] - theirs[i]).max()
                     for i, r in enumerate(rows)])
    assert 1e-4 < off < BF16_MEDIAN_TOLERANCE, off
    # what the tolerance tells apart: the reference's own pass in fp8
    low = reference.reference_logits(params, tc, tokens, rows, low="fp8")
    assert np.median(np.abs(low - theirs).max(-1)) \
        > 2.5 * BF16_MEDIAN_TOLERANCE


def test_a_mixed_dispatch_gives_the_references_logits(model):
    """One program: a chunk for slot 0 (rows 32-63 of its prompt, the state
    its first chunk left), then a span of 4 steps for the lanes of slots 1
    and 2 (slot 0 idle there) — the logits the span's pick sees are the full
    forward's for each lane, and slot 0's state is what its chunk left, not
    what an idle lane of the span would have made of it."""
    tc, config, params = model
    bs, width = 8, 1024 // 8
    prompts = [_prompt(50, 64), _prompt(51, 40), _prompt(52, 17)]
    pool = init_paged_pool(config, 40, bs)
    pk, pv = pool.k, pool.v
    recurrent = paged.Recurrent(None, init_conv_states(config, 3))
    tables = np.zeros((3, width), np.int32)
    for i in range(3):
        tables[i, :12] = np.arange(1 + 12 * i, 13 + 12 * i)
    zero = jnp.zeros((1,), jnp.int32)

    def chunk(pk, pv, rec, slot, start, rows):
        seg = np.zeros((1, 32), np.int32)
        seg[0, :rows] = prompts[slot][start:start + rows]
        return paged.paged_prefill_step(
            params, config, pk, pv, jnp.asarray(tables[slot][None]),
            jnp.asarray([start]), jnp.ones((1,), bool), jnp.asarray(seg),
            jnp.asarray([rows - 1]), routing=True, recurrent=rec,
            folded=zero, slots=jnp.asarray([slot]))

    firsts = {}
    for slot, start, rows in ((0, 0, 32), (1, 0, 32), (1, 32, 8),
                              (2, 0, 17)):
        logits, pk, pv, _, recurrent = chunk(pk, pv, recurrent, slot, start,
                                             rows)
        firsts[slot] = int(np.argmax(np.asarray(logits[0])))
    after_first_chunk = np.asarray(recurrent.states[0][0])
    seen = []

    def pick(logits, temps, keys):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits,
                           ordered=True)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    seg = jnp.asarray(prompts[0][32:64][None])
    lengths = jnp.asarray([0, 40, 17], jnp.int32)
    out = jax.jit(lambda pk, pv, rec: paged.paged_mixed_step(
        params, config, pick, 4, None, pk, pv, jnp.asarray(tables[0][None]),
        jnp.asarray([32]), seg, jnp.asarray([31]),
        jnp.zeros((1,), jnp.float32), jnp.zeros((1, 2), jnp.uint32),
        jnp.asarray(tables), lengths, jnp.asarray([False, True, True]),
        jnp.asarray([0, firsts[1], firsts[2]], jnp.int32),
        jnp.zeros((3,), jnp.float32), jnp.zeros((3, 4, 2), jnp.uint32),
        jnp.asarray([0, 9, 9], jnp.int32), routing=True, recurrent=rec,
        p_folded=zero, p_slot=jnp.asarray([0]),
        d_folded=jnp.zeros((3,), jnp.int32)))(pk, pv, recurrent)
    p_picked, emitted, pk, pv, routing, recurrent = out
    jax.effects_barrier()
    assert len(seen) == 1 + 4 and routing.shape == (paged.N_STEP_COUNTS,)
    emitted = np.asarray(emitted)
    # the chunk's last row, against the full forward of slot 0's prompt
    theirs = reference.reference_logits(params, tc, prompts[0],
                                        np.asarray([63]))
    assert np.abs(seen[0][0] - theirs[0]).max() < LOGIT_TOLERANCE
    assert int(p_picked[0]) == int(np.argmax(theirs[0]))
    for lane in (1, 2):
        tokens = np.concatenate([prompts[lane], [firsts[lane]],
                                 emitted[:, lane]])
        n = len(prompts[lane])
        theirs = reference.reference_logits(params, tc, tokens,
                                            np.arange(n, n + 4))
        for i in range(4):
            assert np.abs(seen[1 + i][lane] - theirs[i]).max() \
                < LOGIT_TOLERANCE
    # slot 0: moved on by its second chunk, and by nothing after it
    assert not np.array_equal(np.asarray(recurrent.states[0][0]),
                              after_first_chunk)
    alone = chunk(pool.k, pool.v, paged.Recurrent(None, tuple(
        s.at[0].set(f[0]) for s, f in zip(
            init_conv_states(config, 3),
            [jnp.asarray(after_first_chunk)[None]] * 3))), 0, 32, 32)
    assert np.allclose(np.asarray(alone[-1].states[0][0]),
                       np.asarray(recurrent.states[0][0]), atol=1e-6)


def test_two_heads_a_row_attend_as_heads_of_their_own():
    """Queries laid into their head's half of a row of zeros, over keys and
    values paired two heads a row, give what 64-wide heads of their own
    give: the zero half meets the other head's values as exact zeros."""
    rng = np.random.default_rng(9)
    b, c, hd = 2, 1, 64
    q = jnp.asarray(rng.normal(size=(b, 4, c, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, 2, 40, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, 2, 40, hd)), jnp.float32)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk",
                        q.reshape(b, 2, 2, c, hd), k) * hd ** -0.5
    want = jnp.einsum("bhgqk,bhkd->bhgqd", jax.nn.softmax(scores, -1),
                      v).reshape(b, 4, c, hd)
    wide = paged._paired_queries(q, 2, 2)
    assert wide.shape == (b, 4, c, 128)
    rows = lambda x: x.transpose(0, 2, 1, 3).reshape(b, 40, 1, 128) \
        .transpose(0, 2, 1, 3)
    from kubeshare_tpu.models.decoding import _attend_cached

    got = _attend_cached(wide, rows(k), rows(v), jnp.full((b, c), 39),
                         scale=hd ** -0.5)
    got = paged._paired_context(got, 2, 2)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


def test_the_decode_lanes_take_the_paged_kernel(monkeypatch):
    """A view longer than a key block, one query row a lane, a backend that
    can run the kernel (interpreted here): ``attend_path`` says "kernel" for
    the paired pool, and a decode step's logits are the key-block loop's."""
    tc = {**TC, "dtype": "bfloat16"}
    config, params = _config(dtype="bfloat16"), weights.make_weights(13, tc)
    bs, width = 16, 1024 // 16
    pool = init_paged_pool(config, 2 + 3 * 8, bs)
    assert pool.k.shape == (2, 26, 1, 16, 128)
    tables = np.zeros((3, width), np.int32)
    tables[0, :8] = np.arange(1, 9)
    tables[2, :8] = np.arange(9, 17)
    rng = np.random.default_rng(4)
    pk = jnp.asarray(rng.normal(size=pool.k.shape), jnp.bfloat16)
    pv = jnp.asarray(rng.normal(size=pool.v.shape), jnp.bfloat16)
    recurrent = paged.Recurrent(None, init_conv_states(config, 3))
    args = (jnp.asarray(tables), jnp.asarray([100, 0, 37], jnp.int32),
            jnp.asarray([True, False, True]),
            jnp.asarray([5, 0, 9], jnp.int32))

    def logits():
        return np.asarray(paged.paged_decode_step(
            params, config, pk, pv, *args, routing=True,
            recurrent=recurrent)[0], np.float32)

    assert paged.attend_path("gqa_moe", 1, width, pk, pv, 128) == "blocks"
    loop = logits()
    monkeypatch.setattr(paged, "_kernel_mode", lambda: "interpret")
    assert paged.attend_path("gqa_moe", 1, width, pk, pv, 128) == "kernel"
    kernel = logits()
    # bf16 contexts rounded once on either path: a few 1e-3 on logits
    assert np.abs(kernel[[0, 2]] - loop[[0, 2]]).max() < 0.05


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

REQUESTS = [(100, 40), (250, 6), (7, 70), (33, 31), (64, 64)]


def test_the_engine_serves_what_the_reference_puts_first(model):
    """Five requests through ``submit`` / ``run`` on three slots — a
    prompt under a chunk and one of eight, lanes idle beside live ones as
    requests end, slots reused by a later request after a longer first, in
    mixed dispatches: in float32 every served token is the reference's
    best."""
    tc, config, params = model
    engine = _engine(config, params)
    engine.warmup()
    warm = engine.compile_counts()
    assert warm["prefill"] == warm["mixed"] == 3  # buckets 8, 16, 32
    assert engine.prefix_index is None  # a match would need the state there
    assert engine.pool.k.shape == (2, 121, 1, 8, 128)
    assert [s.shape for s in engine.states] == [(3, 2, 64)] * 3
    results = [(prompt, engine.submit(Request(f"r{i}", prompt, new)))
               for i, (prompt, new) in enumerate(
                   (_prompt(20 + i, p), n)
                   for i, (p, n) in enumerate(REQUESTS))]
    since = profiling.spans()[-1][1] if profiling.spans() else 0.0
    engine.run()
    assert engine.compile_counts() == warm
    for (prompt, result), (_, new) in zip(results, REQUESTS):
        assert len(result.tokens) == new
        assert _gaps(params, tc, prompt, result.tokens).max() == 0.0
    assert engine.allocator.blocks_in_use == 0
    assert engine.conv_state_resets == len(REQUESTS)
    spans = [r for r in profiling.spans(since=since,
                                        name="kubeshare.engine.conv")]
    assert spans and all(
        set(r[4]) == {"lanes", "passes", "state_reads", "resets", "chunk"}
        for r in spans)
    assert sum(r[4]["resets"] for r in spans) == len(REQUESTS)
    assert sum(r[4]["state_reads"] for r in spans) \
        == engine.conv_state_reads > 0
    # three convolution layers: a decode lane reads 3 states a step, a
    # chunk past row 0 three
    chunks = sum(-(-p // 32) for p, _ in REQUESTS)
    assert sum(r[4]["state_reads"] for r in spans if not r[4]["passes"]) \
        <= 3 * (chunks - len(REQUESTS))
    launches = [r for r in profiling.spans(
        since=since, name="kubeshare.engine.launch")]
    assert {r[4]["attend"] for r in launches} <= {"whole", "blocks"}
    assert {r[4]["experts"] for r in launches} == {"loop"}
    names = {f.name for f in engine.collect_metrics()}
    assert {"kubeshare_serving_conv_state_reads_total",
            "kubeshare_serving_conv_state_resets_total"} <= names


def test_a_reused_slot_starts_from_zeros_whatever_it_held(model):
    """One slot: a long request, then a short one in the same slot.  The
    second serves what it serves alone in a fresh engine, token for token,
    and nothing zeroes the states in between."""
    tc, config, params = model
    first, second = _prompt(61, 90), _prompt(62, 11)
    alone = _engine(config, params, num_slots=1)
    expected = alone.submit(Request("alone", second, 20))
    alone.run()
    engine = _engine(config, params, num_slots=1)
    long = engine.submit(Request("long", first, 25))
    engine.run()
    held = [np.asarray(s) for s in engine.states]
    assert all(np.abs(h[0]).max() > 0 for h in held)
    short = engine.submit(Request("short", second, 20))
    engine.run()
    assert short.tokens == expected.tokens
    assert _gaps(params, tc, first, long.tokens).max() == 0.0
    assert _gaps(params, tc, second, short.tokens).max() == 0.0
    assert engine.conv_state_resets == 2


def test_a_preempted_request_serves_the_tokens_it_would_have(model):
    """A Guarantee admission with no free slot preempts the lane; its state
    is dropped with its pages, and the resumed request prefills prompt +
    generated from row 0 (a reset) and serves the unpreempted stream, no
    token twice."""
    tc, config, params = model
    tenants = TenantRegistry([TenantSpec("gold"), TenantSpec(
        "batch", qos_class=QOS_OPPORTUNISTIC)])
    prompt, gold = _prompt(41, 70), _prompt(42, 40)
    alone = _engine(config, params, num_slots=1)
    expected = alone.submit(Request("alone", prompt, 60))
    alone.run()
    engine = _engine(config, params, num_slots=1, tenants=tenants)
    engine.warmup()
    warm = engine.compile_counts()
    victim = engine.submit(Request("victim", prompt, 60, tenant="batch"))
    while engine.tokens_generated < 30:
        assert engine.step()
    assert not victim.done
    served = engine.submit(Request("gold", gold, 9, tenant="gold"))
    engine.run()
    assert engine.preemptions == {"batch": 1}
    assert victim.tokens == expected.tokens and len(victim.tokens) == 60
    assert _gaps(params, tc, prompt, victim.tokens).max() == 0.0
    assert _gaps(params, tc, gold, served.tokens).max() == 0.0
    assert engine.tokens_generated == 60 + 9
    assert engine.conv_state_resets == 3  # victim, gold, victim again
    assert engine.allocator.blocks_in_use == 0
    assert engine.compile_counts() == warm


@pytest.mark.parametrize("changes,said", [
    (dict(speculative=True), "speculative=True"),
    (dict(steps_per_launch=2), "steps_per_launch > 1"),
    (dict(mesh_spec=MeshSpec(tp=2)), "mesh_spec"),
    (dict(host_tier_bytes=1 << 20), "host_tier_bytes"),
    (dict(pool_role="prefill", mixed=False), "pool_role='prefill'"),
    (dict(pool_role="decode", mixed=False), "pool_role='decode'"),
    (dict(autotune=True), "autotune=True"),
])
def test_what_cannot_carry_a_state_is_refused(model, changes, said):
    _, config, params = model
    with pytest.raises(ValueError, match=said) as refused:
        _engine(config, params, **changes)
    assert "the short convolutions' windows" in str(refused.value)


def test_a_shared_host_tier_is_refused(model):
    from kubeshare_tpu.serving.kv_tier import HostTier, LRUTierPolicy

    _, config, params = model
    with pytest.raises(ValueError, match="a shared host tier"):
        ServingEngine(params, config, EngineConfig(
            num_slots=2, block_size=8, num_blocks=20, max_request_len=128,
            prefill_chunk=32), shared_host_tier=HostTier(
                1 << 20, LRUTierPolicy()))


# ---------------------------------------------------------------------------
# the stage table
# ---------------------------------------------------------------------------

def test_the_mechanisms_scopes_are_one_stage(model):
    """``short_conv`` and ``conv_state`` are ONE stage, ``conv``; the
    attention layers keep ``attention`` / ``kv_write``, the feed-forwards
    ``ffn`` / ``experts``."""
    assert {scope for scope, stage in stages.STAGE_OF_SCOPE.items()
            if stage == "conv"} == {"short_conv", "conv_state"}
    assert stages.STAGES[-3:] == ("retention", "conv", "unscoped")
    assert stages.stage_of("jit(f)/short_conv/dot_general") == "conv"
    assert stages.stage_of("jit(f)/attention/conv_state/gather") == "conv"
    _, config, params = model
    engine = _engine(config, params)
    engine.warmup()
    table = stages.stage_table(stages.program_name("mixed", 32))
    assert {"conv", "attention", "kv_write", "ffn", "experts", "head"} \
        <= set(table.values())
