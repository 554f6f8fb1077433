"""The 'latent_moe' block at tiny widths — single latent (MLA) layers
without rank factors, a leading dense layer, then a router that scores by
sigmoid, chooses by score + bias and weighs by the unbiased scores
renormalised, over experts that are all held, beside one shared expert —
served through the paged latent cache, against the plain reference of the
benchmark (``chipbench/joyai_llm_flash_reference.py``: float32, the expanded
attention, every expert on every row, nothing imported from the program).
What both latent kinds must do alike (an idle lane chooses nothing, a lane
does not see its neighbours, the pool's rows, the counters and the span) is
one parametrised test over the two kinds (``tests/latent_kinds.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from latent_kinds import (BLOCK, KINDS, ROWS, STEPS, config_of, lane_tables,
                          params_of, served_logits)
from kubeshare_tpu.models.transformer import (gated_ffn, transformer_apply,
                                              transformer_init)
from kubeshare_tpu.ops.moe import (ROUTING_COUNTS, expert_tile_rows,
                                   routed_experts_apply, router_choices)
from kubeshare_tpu.serving import (EngineConfig, Request, ServingEngine,
                                   paged)
from kubeshare_tpu.serving.kv_blocks import init_paged_pool, kv_row_layout
from kubeshare_tpu.utils import profiling

KIND = "latent_moe"
TC = KINDS[KIND].tc
reference = KINDS[KIND].reference
paged_prefill_step, paged_decode_step = STEPS
BOTH = pytest.mark.parametrize("kind", sorted(KINDS))
HELD, ZERO, ABSENT, TOUCHED, TILES, TILE_ROWS = range(len(ROUTING_COUNTS))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, TC["vocab_size"], 40) \
        .astype(np.int32)


class TestAgainstThePlainReference:
    PROMPT = 27  # three whole chunks of 8 and a ragged one

    def test_float32_program_is_the_reference(self, tokens):
        """In float32 the paged path — chunked prefill into the latent
        pool, then decode in the absorbed form, the experts in tiles —
        and the reference's full forward differ by summation order alone:
        1e-4 on logits of standard deviation 1 (they read 1e-5).  A rank
        factor left in, the bias weighing, no renormalisation, the shared
        expert left out or the dense layer routed moves logits by 0.05
        and more."""
        params, config = params_of(KIND, 11, jnp.float32), \
            config_of(KIND, "float32")
        served = served_logits(params, config, tokens, self.PROMPT)
        rows = np.arange(self.PROMPT - 1, len(tokens))
        ref = reference.reference_logits(params, TC, tokens, rows)
        assert np.abs(served - ref).max() < 1e-4
        unpaged = np.asarray(transformer_apply(
            params, jnp.asarray(tokens)[None], config)[0])[rows]
        assert np.abs(unpaged - ref).max() < 1e-4

    @pytest.mark.parametrize("wrong", [
        {"mla_rank_scaling": True}, {"router_renormalise": False},
        {"router_scoring": "softmax"}, {"routed_scaling_factor": 1.0}])
    def test_another_law_is_not_the_reference(self, tokens, wrong):
        params = params_of(KIND, 11, jnp.float32)
        served = served_logits(params, config_of(KIND, "float32", **wrong),
                               tokens, self.PROMPT)
        rows = np.arange(self.PROMPT - 1, len(tokens))
        ref = reference.reference_logits(params, TC, tokens, rows)
        assert np.abs(served - ref).max() > 0.05

    def test_bfloat16_program_is_nearer_than_fp8(self, tokens):
        """As served (bf16) the logits lie a mean 0.014-0.036 from the
        reference's over three seeds (rounding, and a router choice
        decided the other way now and then: up to 1.0 at one row, so the
        mean is what is held); the reference's own pass in fp8 lies
        0.26-0.29 from it.  The limit is their geometric middle: a
        program computing in fp8 fails it."""
        params, config = params_of(KIND, 11, jnp.bfloat16), \
            config_of(KIND, "bfloat16")
        served = served_logits(params, config, tokens, self.PROMPT)
        rows = np.arange(self.PROMPT - 1, len(tokens))
        ref = reference.reference_logits(params, TC, tokens, rows)
        low = reference.reference_logits(params, TC, tokens, rows, low="fp8")
        limit = 0.1
        assert np.abs(served - ref).mean() < limit
        assert np.abs(low - ref).mean() > limit


def _expert_case(seed=4, n=24):
    layer = params_of(KIND, seed, jnp.float32)["layers"][1]
    y = jnp.asarray(np.random.default_rng(seed).normal(
        size=(n, TC["d_model"])), jnp.float32)
    return layer["moe"], layer["shared"], y


def _apply(moe, y, first_held=0, live=None):
    return routed_experts_apply(
        moe, y, n_routed=TC["n_routed_experts"], top_k=TC["router_top_k"],
        scale=TC["routed_scaling_factor"], first_held=first_held,
        scoring=TC["router_scoring"], renormalise=TC["router_renormalise"],
        live=live)


def _sizes(**changes):
    return reference._sizes({**TC, **changes})


def _ffn(ffn, y):
    return reference._swiglu(y, ffn["w_gate"], ffn["w_up"], ffn["w_down"])


def test_the_shares_add_up_to_the_uncut_layer():
    """Over 4 chips that would share the layer, each holding 4 of the 16
    routed experts: the held parts summed, and the shared expert (which
    every chip computes alike) counted once, are the reference's uncut
    layer, to float32 rounding."""
    moe, shared, y = _expert_case()
    whole = reference.routed_experts(y, moe, _sizes()) + _ffn(shared, y)
    total = gated_ffn(shared, y, jnp.float32)
    counts = np.zeros((len(ROUTING_COUNTS),), np.int64)
    for rank in range(4):
        share = {k: (v if k in ("router", "bias") else v[4 * rank:4 * rank + 4])
                 for k, v in moe.items()}
        out, c = _apply(share, y, first_held=4 * rank)
        np.testing.assert_allclose(
            out, reference.routed_experts(
                y, share, _sizes(first_expert_held=4 * rank)), atol=1e-5)
        total = total + out
        counts += np.asarray(c)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    # every choice is held by exactly one share; there is no zero expert
    n_choices = y.shape[0] * TC["router_top_k"]
    assert counts[HELD] == n_choices and counts[ZERO] == 0
    assert counts[ABSENT] == 3 * n_choices
    uncut = np.asarray(_apply(moe, y)[1])
    assert list(uncut[:3]) == [n_choices, 0, 0]
    # 24 rows x 4 choices over 16 outputs: 6 rows an expert, tiles of 16
    tile = expert_tile_rows(y.shape[0], TC["router_top_k"],
                            moe["router"].shape[1])
    assert tile == 16
    assert uncut[TILE_ROWS] == uncut[TILES] * tile >= uncut[HELD]


def test_the_bias_chooses_and_never_weighs():
    """A bias that lifts an expert over the row's top_k-th score puts it
    among the chosen; the weights stay the UNBIASED scores, renormalised
    over the chosen and scaled, whatever the bias was."""
    logits = jnp.asarray([[2.0, 1.5, 1.0, 0.5, 0.0, -0.5, -1.0, -3.0]])
    law = dict(top_k=3, scale=2.5, scoring="sigmoid", renormalise=True)
    scores = np.asarray(jax.nn.sigmoid(logits))[0]

    def weights_of(chosen):
        return 2.5 * scores[chosen] / (scores[chosen].sum() + 1e-20)

    gate, index = router_choices(logits, jnp.zeros((8,)), **law)
    assert list(np.asarray(index[0])) == [0, 1, 2]
    np.testing.assert_allclose(gate[0], weights_of([0, 1, 2]), rtol=1e-6)
    bias = jnp.zeros((8,)).at[7].set(0.9)  # sigmoid(-3) + 0.9 > sigmoid(1.5)
    gate, index = router_choices(logits, bias, **law)
    assert list(np.asarray(index[0])) == [7, 0, 1]
    np.testing.assert_allclose(gate[0], weights_of([7, 0, 1]), rtol=1e-6)
    assert float(gate[0, 0]) < 0.1  # the lifted expert weighs its own score
    np.testing.assert_allclose(gate.sum(), 2.5, rtol=1e-6)
    # no bias at all is the zero bias
    none = router_choices(logits, None, **law)
    zero = router_choices(logits, jnp.zeros((8,)), **law)
    assert all(np.array_equal(a, b) for a, b in zip(none, zero))
    # in the layer: the seeded bias moves choices, and the reference agrees
    moe, _, y = _expert_case()
    unbiased = {**moe, "bias": jnp.zeros_like(moe["bias"])}
    out, _ = _apply(moe, y)
    np.testing.assert_allclose(
        out, reference.routed_experts(y, moe, _sizes()), atol=1e-5)
    assert np.abs(out - _apply(unbiased, y)[0]).max() > 1e-2


def _forced_router(moe, chosen):
    """The router with every row's scores pinned on ``chosen`` (for rows
    of positive values)."""
    router = np.asarray(moe["router"]).copy() * 1e-3
    router[:, chosen] += np.linspace(2.0, 1.0, len(chosen))[None, :]
    return {**moe, "router": jnp.asarray(router),
            "bias": jnp.zeros_like(moe["bias"])}


def test_nothing_is_dropped_at_any_skew():
    """Every row sent to the same four experts — 300 rows, three tiles of
    128 rows an expert (75 rows an expert if they chose evenly: the
    largest tile), the last one padded: the result is still the
    reference's, which runs every expert on every row."""
    moe, _, y = _expert_case(n=300)
    y = jnp.abs(y)  # so that the pinned scores win on every row
    for chosen in ([0, 1, 2, 3], [0, 5, 9, 13]):
        forced = _forced_router(moe, chosen)
        out, counts = _apply(forced, y)
        np.testing.assert_allclose(
            out, reference.routed_experts(y, forced, _sizes()), atol=2e-5)
        assert list(np.asarray(counts)) == [300 * 4, 0, 0, 4, 12, 12 * 128]
    out, counts = routed_experts_apply(
        _forced_router(moe, [0, 1, 2, 3]), y[:24], n_routed=16, top_k=4,
        scale=2.5, scoring="sigmoid", renormalise=True,
        live=jnp.arange(24) < 9)
    # 9 live rows of 24: the tile is the shapes' (24 rows x 4 choices
    # over 16 outputs: 16), whatever lives; the dead rows read 0
    assert list(np.asarray(counts)) == [9 * 4, 0, 0, 4, 4, 4 * 16]
    assert not np.asarray(out[9:]).any()


def _prefilled(kind, tokens, lanes=3, rows=9, **changes):
    params, config = params_of(kind, 7, jnp.bfloat16, **changes), \
        config_of(kind, "bfloat16", **changes)
    pool = init_paged_pool(config, 1 + lanes * ROWS // BLOCK, BLOCK)
    tables = lane_tables(lanes)
    pk, pv = pool.k, pool.v
    for lane in range(lanes):
        _, pk, pv = paged_prefill_step(
            params, config, pk, pv, tables[lane][None], jnp.asarray([0]),
            jnp.ones((1,), bool),
            jnp.asarray(tokens[rows * lane:rows * lane + rows][None]),
            jnp.asarray([rows - 1]))
    return params, config, pk, pv, tables


@pytest.mark.parametrize("path", ["loop", "kernel"])
@BOTH
def test_a_lanes_logits_do_not_depend_on_its_co_batched_lanes(
        kind, tokens, path, monkeypatch):
    """Routing is a function of the row alone and nothing has a capacity,
    so a decode lane reads the same logits whatever rides beside it —
    with the experts' tiles in the loop, and in the grouped kernel (the
    twin widened to widths the kernel reads, d 128 and experts of 128:
    programs of their own, traced under the patched mode alone)."""
    wide = {}
    if path == "kernel":
        monkeypatch.setattr(paged, "_kernel_mode", lambda: "interpret")
        wide = dict(d_model=128, expert_d_ff=128)
    params, config, pk, pv, tables = _prefilled(kind, tokens, **wide)
    assert paged.experts_path(params["layers"][-1]["moe"], 3) == path
    lengths = jnp.full((3,), 9, jnp.int32)
    toks = jnp.asarray(tokens[30:33])
    alone = paged_decode_step(
        params, config, pk, pv, tables, lengths,
        jnp.asarray([False, True, False]), toks)[0]
    together = paged_decode_step(
        params, config, pk, pv, tables, lengths,
        jnp.asarray([True, True, True]), toks)[0]
    np.testing.assert_array_equal(alone[1], together[1])


@BOTH
def test_an_idle_lane_and_a_chunks_padding_choose_nothing(kind, tokens):
    """The counts of a step are those of its live rows: ``held + zero +
    absent`` is ``top_k`` x expert layers x the rows that chose, and an
    idle lane or a chunk's padded tail adds to none of them."""
    params, config, pk, pv, tables = _prefilled(kind, tokens)
    per_row = config.router_top_k * config.expert_layers
    lengths = jnp.full((3,), 9, jnp.int32)
    toks = jnp.asarray(tokens[30:33])

    def decode(active):
        return np.asarray(paged_decode_step(
            params, config, pk, pv, tables, lengths, jnp.asarray(active),
            toks, routing=True)[3])

    one, three = decode([False, True, False]), decode([True, True, True])
    assert one[-1] == 1 and three[-1] == 3
    for counts in (one, three):
        assert counts[HELD] + counts[ZERO] + counts[ABSENT] \
            == per_row * counts[-1]
        assert counts[TILE_ROWS] >= counts[HELD] >= counts[TOUCHED]
    assert not decode([False, False, False]).any()
    # a 5-row prompt in a chunk of 8: 3 rows of padding
    chunk = np.zeros((1, 8), np.int32)
    chunk[0, :5] = tokens[:5]
    counts = np.asarray(paged_prefill_step(
        params, config, pk, pv, tables[:1], jnp.asarray([0]),
        jnp.ones((1,), bool), jnp.asarray(chunk), jnp.asarray([4]),
        routing=True)[3])
    assert counts[-1] == 5
    assert counts[HELD] + counts[ZERO] + counts[ABSENT] == per_row * 5


@BOTH
def test_the_pool_holds_one_row_a_sub_layer(kind):
    """A latent row a sub-layer in K; in V two sub-layers' rotary keys
    side by side in one row, the last row's second half spare where the
    count is odd (3 single layers) — and the layout counts it."""
    config, tc = config_of(kind, "bfloat16"), KINDS[kind].tc
    subs = {"latent_shortcut": 4, "latent_moe": 3}[kind]
    layout = kv_row_layout(config)
    assert (layout.kind, layout.layers) == ("latent", subs)
    assert config.attn_sublayers == subs
    pool = init_paged_pool(config, 9, BLOCK)
    assert pool.k.shape == (subs, 9, 1, BLOCK, tc["kv_lora_rank"])
    assert pool.v.shape == (2, 9, 1, BLOCK, 2 * tc["qk_rope_head_dim"])
    assert pool.bytes_per_block() == layout.values_per_row() * 2 * BLOCK \
        == (subs * 16 + 2 * 16) * 2 * BLOCK
    assert int(pool.k.nbytes + pool.v.nbytes) == 9 * pool.bytes_per_block()


def _engine(kind, dtype="bfloat16", **changes):
    config = config_of(kind, dtype)
    params = params_of(kind, 5, jnp.dtype(dtype))
    ec = EngineConfig(**{**dict(num_slots=3, block_size=BLOCK, num_blocks=64,
                                max_request_len=ROWS, prefill_chunk=8),
                         **changes})
    return ServingEngine(params, config, ec), params, config


@BOTH
def test_the_engines_counters_and_spans_add_up(kind):
    """Every routed dispatch leaves one ``kubeshare.engine.routing`` span
    whose counts add up to top_k x expert layers x the rows that chose
    (``live``, at most the ``rows`` its passes carried), whose tiles hold
    its held assignments, and the engine's counters are their sums."""
    engine, params, config = _engine(kind)
    tc = KINDS[kind].tc
    engine.warmup()
    rng = np.random.default_rng(2)
    reqs = [(f"r{i}", rng.integers(0, tc["vocab_size"], n), new)
            for i, (n, new) in enumerate([(5, 6), (13, 4), (21, 9), (3, 3)])]
    since = profiling.spans()[-1][2] if profiling.spans() else 0.0
    for rid, prompt, new in reqs:
        engine.submit(Request(rid, prompt, new))
    out = engine.run()
    assert all(len(out[rid].tokens) == new for rid, _, new in reqs)
    assert engine.compile_counts() == {
        **engine.compile_counts(), "verify": 0, "loop": 0}
    routed = [attrs for name, start, _, _, attrs in profiling.spans()
              if name == "kubeshare.engine.routing" and start >= since]
    per_row = tc["router_top_k"] * config.expert_layers
    held_here = config.held_experts
    assert routed and all(
        a["held"] + a["zero"] + a["absent"] == per_row * a["live"]
        and 0 < a["live"] <= a["rows"]
        and a["tile_rows"] >= a["held"] >= a["touched"]
        and a["tiles"] >= a["touched"]
        and 0 <= a["touched"] <= a["passes"] * config.expert_layers
        * held_here
        for a in routed)
    # a lane that has finished, or was never filled, rides every span
    assert any(a["live"] < a["rows"] for a in routed)
    if kind == "latent_moe":  # every expert is held: nothing is elsewhere
        assert all(a["zero"] == 0 and a["absent"] == 0 for a in routed)
    for name in ("held", "zero", "absent"):
        assert engine.moe_assignments[name] == sum(a[name] for a in routed)
    assert engine.moe_experts_touched == sum(a["touched"] for a in routed)
    assert engine.moe_tiles == sum(a["tiles"] for a in routed)
    assert engine.moe_tile_rows == sum(a["tile_rows"] for a in routed)
    assert engine.moe_passes == sum(a["passes"] for a in routed)
    dispatches = (engine.prefill_chunks + engine.decode_steps
                  - engine.mixed_steps)
    assert len(routed) == dispatches
    families = {f.name: f for f in engine.collect_metrics()}
    by_kind = {s.labels["kind"]: s.value for s in families[
        "kubeshare_serving_moe_assignments_total"].samples}
    assert by_kind == engine.moe_assignments
    for family, count in (("experts_touched", engine.moe_experts_touched),
                          ("tiles", engine.moe_tiles),
                          ("tile_rows", engine.moe_tile_rows)):
        assert families[f"kubeshare_serving_moe_{family}_total"] \
            .samples[0].value == count


def test_the_float32_engine_serves_the_unpaged_forwards_stream():
    """The engine in float32 — chunked prefill, mixed dispatches, decode
    spans — emits what the unpaged forward would, token by token."""
    engine, params, config = _engine(KIND, "float32")
    rng = np.random.default_rng(3)
    reqs = [(f"r{i}", rng.integers(0, TC["vocab_size"], n), new)
            for i, (n, new) in enumerate([(5, 6), (13, 4), (21, 5)])]
    for rid, prompt, new in reqs:
        engine.submit(Request(rid, prompt, new))
    out = engine.run()
    # one shape: rows after the last real one are causally dead
    forward = jax.jit(lambda t: transformer_apply(params, t, config))
    for rid, prompt, new in reqs:
        toks = list(prompt)
        for _ in range(new):
            padded = np.zeros((1, 32), np.int32)
            padded[0, :len(toks)] = toks
            toks.append(int(jnp.argmax(
                forward(jnp.asarray(padded))[0, len(toks) - 1])))
        assert out[rid].tokens == toks[len(prompt):], rid


@BOTH
def test_transformer_init_makes_the_pytree_the_benchmark_serves(kind):
    config = config_of(kind, "float32")
    made = transformer_init(jax.random.PRNGKey(0), config)
    served = KINDS[kind].weights.make_weights(0, KINDS[kind].tc)
    assert jax.tree.structure(made) == jax.tree.structure(served)
    assert jax.tree.map(lambda a: a.shape, made) \
        == jax.tree.map(lambda a: a.shape, served)
    assert config.latent and config.routed
    assert config.expert_layers == {"latent_shortcut": 2,
                                    "latent_moe": 2}[kind]


def test_the_fields_of_one_kind_are_refused_by_the_other():
    with pytest.raises(ValueError, match="latent_moe"):
        config_of("latent_shortcut", "float32", n_shared_experts=1)
    with pytest.raises(ValueError, match="latent_moe"):
        config_of("latent_shortcut", "float32", first_dense_layers=1)
    with pytest.raises(ValueError, match="first_dense_layers"):
        config_of(KIND, "float32", first_dense_layers=4)
    with pytest.raises(ValueError, match="router_scoring"):
        config_of(KIND, "float32", router_scoring="tanh")
    dense = config_of(KIND, "float32", first_dense_layers=3)
    assert dense.latent and not dense.routed
