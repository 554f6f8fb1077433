"""Generation by diffusion over blocks (``TransformerConfig.diffusion_block``)
through the 'gqa_moe' block: K/V-a-head attention at an explicit head width
with per-head q/k norms, the routed experts as every layer's feed-forward,
a block-causal mask, lanes that hold a block state and passes that commit
0..B tokens a lane.

Everything is held to the plain float32 reference the benchmark judges the
cell by (``chipbench/sdar_30b_a3b_chat_reference.py``, nothing of the program
in it) on the benchmark's seeded weights at the twin's widths
(``chipbench/tests/configs/tiny_sdar.json``), served in float32.  **The
tolerances**: the weights are bf16 values held in float32 and both sides
compute in float32, so they differ by the order of their sums alone — the
program's softmax is carried across key blocks and its experts add a row's
choices tile by tile, the reference takes whole rows at ``highest``: 2e-4 on
a logit of size 1-4 is fifty times what was read (4e-6) and a hundredth of
the smallest gap a wrong token reads (0.03).
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import sdar_30b_a3b_chat_reference as reference  # noqa: E402
from chipbench import sdar_30b_a3b_chat_weights as weights  # noqa: E402
from kubeshare_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, attend_reach, transformer_apply, transformer_init)
from kubeshare_tpu.parallel.mesh import MeshSpec  # noqa: E402
from kubeshare_tpu.serving import (  # noqa: E402
    QOS_OPPORTUNISTIC, EngineConfig, Request, ServingEngine, TenantRegistry,
    TenantSpec, paged)
from kubeshare_tpu.serving.kv_blocks import init_paged_pool  # noqa: E402
from kubeshare_tpu.utils import profiling  # noqa: E402

with open(os.path.join(REPO, "chipbench", "tests", "configs",
                       "tiny_sdar.json")) as f:
    TWIN = json.load(f)["transformer_config"]
LOGIT_TOLERANCE = 2e-4
MASK = TWIN["mask_token"]


def _tc(block: int = 4, **changes):
    return {**TWIN, "dtype": "float32", "diffusion_block": block,
            "diffusion_steps": block, **changes}


def _config(tc) -> TransformerConfig:
    return TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})


@pytest.fixture(scope="module")
def models():
    """(tc, config, params) by block length, one seed of weights each."""
    out = {}
    for block in (2, 4):
        tc = _tc(block)
        out[block] = (tc, _config(tc), weights.make_weights(11, tc))
    return out


def _engine(config, params, **changes) -> ServingEngine:
    kwargs = dict(num_slots=4, block_size=16, num_blocks=41,
                  max_request_len=128, prefill_chunk=16)
    tenants = changes.pop("tenants", None)
    kwargs.update(changes)
    return ServingEngine(params, config, EngineConfig(**kwargs),
                         tenants=tenants)


def _prompt(seed: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 500, length).astype(
        np.int32)


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------

def test_the_block_has_the_published_shape():
    config = _config(_tc())
    assert config.head_dim == 32 != config.d_model // config.n_heads
    assert (config.expert_layers, config.routed, config.latent) \
        == (3, True, False)
    params = transformer_init(jax.random.PRNGKey(0), config)
    layer = params["layers"][0]
    assert set(layer) == {"attn", "norm1", "norm2", "moe"}  # no dense MLP
    assert layer["attn"]["wq"].shape == (64, 4, 32)
    assert layer["attn"]["wk"].shape == (64, 2, 32)
    assert layer["attn"]["wo"].shape == (4, 32, 64)
    assert layer["attn"]["q_norm"]["scale"].shape == (32,)
    assert layer["moe"]["w_gate"].shape == (16, 64, 32)
    made = weights.make_weights(3, _tc())
    assert jax.tree.structure(made) == jax.tree.structure(params)
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, params)
    pool = init_paged_pool(config, 5, 16)
    assert pool.k.shape == pool.v.shape == (3, 5, 2, 16, 32)
    assert config.transfer_counts() == (1, 1, 1, 1)
    assert dataclasses.replace(
        config, diffusion_block=8, diffusion_steps=3).transfer_counts() \
        == (3, 3, 2)


def test_reach_is_the_whole_mask():
    positions = jnp.arange(10)[None]
    assert list(np.asarray(attend_reach(_config(_tc(4)), positions))[0]) \
        == [3, 3, 3, 3, 7, 7, 7, 7, 11, 11]
    causal = _config(_tc(0, diffusion_steps=0, mask_token=0))
    assert attend_reach(causal, positions) is positions


@pytest.mark.parametrize("changes,said", [
    (dict(diffusion_steps=0), "diffusion_steps must be in"),
    (dict(diffusion_steps=5), "diffusion_steps must be in"),
    (dict(mask_token=512), "mask_token 512 is not among"),
    (dict(diffusion_block=0), "mean nothing without it"),
    (dict(moe_every=2), "takes neither moe_every"),
    (dict(positional="learned"), "positional='rope'"),
    (dict(head_width=31), "head_width must be even"),
    (dict(n_kv_heads=3), "multiple of n_kv_heads"),
    (dict(n_shared_experts=1), "routed experts alone"),
    (dict(router_top_k=17), "router_top_k must be in"),
    (dict(block="dense", n_routed_experts=0, router_top_k=0,
          rope_theta=10000.0), "are block 'gqa_moe''s"),
    (dict(block="gqa"), "block must be 'dense', 'gqa_moe'"),
])
def test_a_configuration_that_makes_no_sense_is_refused(changes, said):
    with pytest.raises(ValueError, match=said):
        _config({**_tc(), **changes})


def test_the_dense_cache_decoder_refuses_the_block():
    from kubeshare_tpu.models.decoding import init_kv_cache

    with pytest.raises(ValueError, match="no dense-cache decoder"):
        init_kv_cache(_config(_tc()), 1)


# ---------------------------------------------------------------------------
# the unpaged forward under the block-causal mask
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [3, 22, 24])
@pytest.mark.parametrize("block", [2, 4])
def test_unpaged_logits_are_the_references(models, block, length):
    """Every row's logits of one pass over a sequence, some rows masked:
    the program's second path against the plain reference."""
    tc, config, params = models[block]
    tokens = _prompt(length, length)
    masked = np.zeros(length, bool)
    masked[-(length % 5 + 1):] = True
    fed = np.where(masked, MASK, tokens)
    got = np.asarray(transformer_apply(params, jnp.asarray(fed)[None],
                                       config))[0]
    want = reference.reference_logits(params, tc, tokens, np.arange(length),
                                      masked=masked)
    assert np.abs(got - want).max() < LOGIT_TOLERANCE
    # the mask is not the causal one: the first row sees its block's last
    causal = dataclasses.replace(config, diffusion_block=0,
                                 diffusion_steps=0, mask_token=0)
    other = np.asarray(transformer_apply(params, jnp.asarray(fed)[None],
                                         causal))[0]
    if length > 3:  # at 3 every row is masked: one value for all keys
        assert np.abs(other[0] - got[0]).max() > 0.05


# ---------------------------------------------------------------------------
# served through the engine: the reference's loop, token for token
# ---------------------------------------------------------------------------

def _serve(engine, requests):
    for i, (prompt, max_new) in enumerate(requests):
        engine.submit(Request(f"r{i}", prompt, max_new))
    out = engine.run()
    return [out[f"r{i}"] for i in range(len(requests))]


def _held_to_the_reference(tc, params, requests, results):
    for (prompt, max_new), result in zip(requests, results):
        assert len(result.tokens) == max_new  # exactly, never a whole block
        assert result.tokens == reference.generate(params, tc, prompt,
                                                   max_new)
        gaps = reference.served_gaps(params, tc, prompt, result.tokens)
        assert gaps.shape == (max_new,) and gaps.max() < LOGIT_TOLERANCE
        assert result.first_token_at is not None
        assert result.finished_at >= result.first_token_at


@pytest.mark.parametrize("block", [2, 4])
def test_served_tokens_are_the_reference_loops(models, block):
    """Prompts whose length is 0, 1 and B - 1 modulo B (one shorter than a
    block: nothing to prefill; one of several chunks), ``max_new`` ending
    inside a block and on its edge, more requests than lanes."""
    tc, config, params = models[block]
    b = block
    shapes = [(5 * b, 3 * b), (5 * b + 1, 2 * b + 1), (6 * b - 1, b + 1),
              (b - 1, 2 * b), (40 + b - 1, 1), (37, 2 * b - 1),
              (16, b), (1, 1)]
    requests = [(_prompt(7 * i + b, p), n) for i, (p, n) in enumerate(shapes)]
    engine = _engine(config, params)
    engine.warmup()
    warm = engine.compile_counts()
    results = _serve(engine, requests)
    _held_to_the_reference(tc, params, requests, results)
    assert engine.compile_counts() == warm  # nothing compiled after warmup
    # a token counts once, when it is served
    served = sum(n for _, n in shapes)
    assert engine.tokens_generated == served \
        == engine.diffusion_tokens_committed
    assert engine.requests_finished == len(shapes)
    assert engine.allocator.blocks_in_use == 0
    # a dispatch counts once as a decode step, twice more when it carries
    # a chunk: the harness classifies dispatches from these
    assert engine.mixed_steps <= engine.prefill_chunks
    assert engine.mixed_steps <= engine.decode_steps
    passes = engine.diffusion_passes
    assert engine.diffusion_rows == b * (passes["denoise"] + passes["commit"])
    assert passes["commit"] == engine.diffusion_blocks


def test_the_harness_warm_requests_compile_nothing(models):
    """``chipbench.run._warm_paths``: two 5-token prompts, one prefilled
    block and a one-token tail each, the second beside the first's lane."""
    tc, config, params = models[4]
    engine = _engine(config, params)
    engine.warmup()
    warm = engine.compile_counts()
    assert warm["decode"] == warm["mixed"] == warm["verify"] == 0
    assert warm["diffusion"] == 1 and warm["mixed_diffusion"] == 3
    first = engine.submit(Request("warm-0", np.arange(1, 6, dtype=np.int32),
                                  12))
    while first.first_token_at is None:
        engine.step()
    engine.submit(Request("warm-1", np.arange(7, 12, dtype=np.int32), 2))
    while engine.step():
        pass
    assert engine.compile_counts() == warm
    assert engine.mixed_steps == 1 and engine.prefill_chunks == 2
    assert len(engine.pop_finished()) == 2


def test_a_prompt_may_hold_the_mask_id(models):
    """Masked-ness is position state, never ``token == mask_token``: a
    prompt whose tail — inside the first generated block — holds the id."""
    tc, config, params = models[4]
    prompt = _prompt(3, 22)
    prompt[[2, 20, 21]] = MASK
    requests = [(prompt, 7)]
    results = _serve(_engine(config, params), requests)
    _held_to_the_reference(tc, params, requests, results)
    # and a program that compared ids would not have served this: with the
    # two known rows taken for masked ones they would be picked anew
    other = prompt.copy()
    other[[20, 21]] = 7
    assert _serve(_engine(config, params), [(other, 7)])[0].tokens \
        != results[0].tokens


def test_one_request_after_another_and_side_by_side(models):
    """What a lane serves does not depend on its neighbours: the same
    requests one at a time, and all at once beside an idle lane."""
    tc, config, params = models[4]
    requests = [(_prompt(40 + i, p), n)
                for i, (p, n) in enumerate([(9, 6), (30, 9), (18, 5)])]
    together = _serve(_engine(config, params), requests)
    for request, result in zip(requests, together):
        alone = _serve(_engine(config, params), [request])[0]
        assert alone.tokens == result.tokens


def test_a_lanes_numbers_do_not_depend_on_its_neighbours(models):
    """The pass itself, bit for bit: lane 1's picks, its commits and the
    K/V rows it wrote, with idle neighbours and beside live ones that
    reach further and hold other tokens."""
    tc, config, params = models[4]
    pool = init_paged_pool(config, 33, 16)
    tables = jnp.asarray(1 + np.arange(32).reshape(4, 8), jnp.int32)
    prefill = jax.jit(paged.paged_diffusion_prefill, static_argnums=(1,))
    step = jax.jit(paged.paged_diffusion_pass, static_argnums=(1,))
    pk, pv = pool.k, pool.v
    for lane, rows in ((0, 32), (1, 16), (2, 48)):
        tokens = jnp.asarray(_prompt(60 + lane, rows))[None]
        pk, pv = prefill(params, config, pk, pv, tables[lane][None],
                         jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool),
                         tokens, jnp.asarray([rows - 1], jnp.int32))
    lengths = jnp.asarray([32, 16, 48, 0], jnp.int32)
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 500, (4, 4)),
                         jnp.int32)
    masked = jnp.asarray([[1, 1, 1, 1], [0, 1, 1, 1], [1, 0, 1, 0],
                          [0, 0, 0, 0]], bool)
    quota = jnp.asarray([1, 2, 1, 0], jnp.int32)

    def run(active):
        picked, commit, k, v = step(params, config, pk, pv, tables, lengths,
                                    jnp.asarray(active), tokens, masked,
                                    masked, quota)
        return (np.asarray(picked[1]), np.asarray(commit[1]),
                np.asarray(k[:, 2, :, :4]), np.asarray(v[:, 2, :, :4]))

    alone = run([False, True, False, False])
    beside = run([True, True, True, False])
    for a, b in zip(alone, beside):
        assert np.array_equal(a, b)
    assert alone[1].sum() == 2 and not alone[1][0]  # of its masked rows
    # an idle lane commits nothing, whatever it is handed
    _, commit, _, _ = step(params, config, pk, pv, tables, lengths,
                           jnp.asarray([False, True, False, False]), tokens,
                           masked, masked, jnp.full((4,), 4, jnp.int32))
    assert not np.asarray(commit)[[0, 2, 3]].any()


def test_the_most_confident_rows_are_committed_first(models):
    """The pick is the argmax, the order the confidence's: against the
    unpaged forward's logits of the same block state."""
    tc, config, params = models[4]
    prompt = _prompt(9, 8)
    pool = init_paged_pool(config, 9, 16)
    table = jnp.asarray(1 + np.arange(8), jnp.int32)[None]
    pk, pv = jax.jit(paged.paged_diffusion_prefill, static_argnums=(1,))(
        params, config, pool.k, pool.v, table, jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), bool), jnp.asarray(prompt)[None],
        jnp.asarray([7], jnp.int32))
    masked = jnp.ones((1, 4), bool)
    picked, commit, _, _ = jax.jit(paged.paged_diffusion_pass,
                                   static_argnums=(1,))(
        params, config, pk, pv, table, jnp.asarray([8], jnp.int32),
        jnp.ones((1,), bool), jnp.zeros((1, 4), jnp.int32), masked, masked,
        jnp.asarray([2], jnp.int32))
    fed = np.concatenate([prompt, np.full(4, MASK, np.int32)])
    logits = np.asarray(transformer_apply(params, jnp.asarray(fed)[None],
                                          config))[0, 8:]
    assert list(np.asarray(picked)[0]) == list(logits.argmax(-1))
    confidence = np.asarray(jax.nn.softmax(logits, -1)).max(-1)
    assert sorted(np.flatnonzero(np.asarray(commit)[0])) \
        == sorted(np.argsort(-confidence)[:2])


@pytest.mark.parametrize("schedule", ["", "index", "reverse", "at_once"])
def test_the_reference_tells_the_schedule_from_the_tokens(models, schedule):
    """Tokens generated most confident rows first fit that schedule better
    than any other the reference forms (``order_gap`` below zero); tokens
    generated by another rule fit THAT one best, and ``order_gap`` is how
    much worse they fit the stated one."""
    tc, _, params = models[4]
    gaps = []
    for seed, (length, new) in enumerate([(9, 14), (22, 9), (40, 12)]):
        prompt = _prompt(seed, length)
        served = reference.generate(params, tc, prompt, new, schedule)
        gaps.append(reference.served_gaps(params, tc, prompt, served))
    read = reference.summarize(gaps)
    assert set(reference.SCHEDULES) <= set(read)
    if not schedule:
        assert read["mean_gap"] < LOGIT_TOLERANCE
        assert read["order_gap"] < -0.01
    else:
        assert read["least_other"] == schedule
        assert read[schedule] < LOGIT_TOLERANCE
        assert read["order_gap"] > 0.01
        assert read["order_gap"] == pytest.approx(read["mean_gap"], abs=1e-3)


# ---------------------------------------------------------------------------
# preemption, the prefix index
# ---------------------------------------------------------------------------

def _registry():
    return TenantRegistry([TenantSpec("gold"),
                           TenantSpec("batch", qos_class=QOS_OPPORTUNISTIC)])


@pytest.mark.parametrize("served_first", [1, 2, 6])
def test_preempted_lanes_resume_from_their_last_committed_block(
        models, served_first):
    """A Guarantee admission the pool cannot fund preempts the diffusion
    lane mid-block; the victim resumes from its last committed block with
    its unfinished block's state, serves the unpreempted stream, and no
    token is served or counted twice."""
    tc, config, params = models[4]
    engine = _engine(config, params, num_slots=2, num_blocks=4,
                     max_request_len=48, tenants=_registry())
    engine.warmup()
    warm = engine.compile_counts()
    p_batch, p_gold = _prompt(71, 18), _prompt(72, 20)
    victim = engine.submit(Request("victim", p_batch, 14, tenant="batch"))
    while engine.tokens_generated < served_first:
        assert engine.step()
    assert not victim.done
    engine.submit(Request("gold", p_gold, 9, tenant="gold"))
    out = engine.run()
    assert engine.preemptions.get("batch", 0) >= 1
    for rid, prompt, new in (("victim", p_batch, 14), ("gold", p_gold, 9)):
        assert out[rid].tokens == reference.generate(params, tc, prompt, new)
    assert engine.tokens_generated == 14 + 9
    assert engine.tenant_tokens == {"batch": 14, "gold": 9}
    assert engine.prefix_hit_requests >= 1  # the resume hit what it left
    assert engine.allocator.blocks_in_use == 0
    assert engine.compile_counts() == warm


def test_a_prefix_hit_is_cut_to_whole_diffusion_blocks(models):
    """Under the block-causal mask a row's keys depend on its whole block:
    of a prompt that shares 22 tokens with a cached one, 20 are taken."""
    tc, config, params = models[4]
    first = _prompt(81, 27)
    second = np.concatenate([first[:22], _prompt(82, 9)])
    third = first[:24].copy()  # the cache covers all it prefills
    requests = [(first, 6), (second, 5), (third, 7)]
    engine = _engine(config, params)
    engine.warmup()
    warm = engine.compile_counts()
    results = []
    for i, (prompt, max_new) in enumerate(requests):
        engine.submit(Request(f"r{i}", prompt, max_new))
        results.append(engine.run()[f"r{i}"])
    _held_to_the_reference(tc, params, requests, results)
    assert engine.prefix_hit_requests == 2
    # 20 of the second's 22 shared tokens; the third's 24 rows, all cached:
    # it prefilled nothing and went straight to its passes
    assert engine.prefix_hit_tokens == 20 + 24
    assert results[2].prefill_chunks == 0
    assert engine.cow_copies >= 1  # 20 rows end inside a 16-row page
    assert engine.compile_counts() == warm
    cold = _serve(_engine(config, params, prefix_cache=False), requests)
    assert [r.tokens for r in cold] == [r.tokens for r in results]


# ---------------------------------------------------------------------------
# what does not serve such lanes yet, and what is said of a dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("changes,said", [
    (dict(speculative=True), "speculative=True"),
    (dict(steps_per_launch=2), "steps_per_launch > 1"),
    (dict(mesh_spec=MeshSpec(tp=2)), "mesh_spec"),
    (dict(host_tier_bytes=1 << 20), "host_tier_bytes"),
    (dict(pool_role="prefill", mixed=False), "pool_role='prefill'"),
    (dict(pool_role="decode", mixed=False), "pool_role='decode'"),
    (dict(autotune=True), "autotune=True"),
    (dict(eos_token=3), "eos_token"),
    (dict(block_size=6, max_request_len=126), "divides block_size 6"),
    (dict(prefill_chunk=18), "prefill_chunk 18"),
    (dict(mixed_prefill_budget=2), "below one diffusion block"),
])
def test_what_does_not_serve_diffusion_lanes_is_refused(models, changes,
                                                        said):
    tc, config, params = models[4]
    with pytest.raises(ValueError, match=said) as refused:
        _engine(config, params, **changes)
    assert "diffusion" in str(refused.value)


def test_more_of_what_is_refused(models):
    tc, config, params = models[4]
    odd = _tc(4, diffusion_block=3, diffusion_steps=3)
    with pytest.raises(ValueError, match="power of two"):
        _engine(_config(odd), params, block_size=12, prefill_chunk=12,
                max_request_len=120)
    from kubeshare_tpu.serving.kv_tier import HostTier, LRUTierPolicy

    with pytest.raises(ValueError, match="a shared host tier"):
        ServingEngine(params, config, EngineConfig(
            num_slots=4, block_size=16, num_blocks=41, max_request_len=128,
            prefill_chunk=16),
            shared_host_tier=HostTier(1 << 20, LRUTierPolicy()))
    engine = _engine(config, params)
    with pytest.raises(ValueError, match="greedy requests only"):
        engine.submit(Request("hot", _prompt(1, 9), 4, temperature=0.7,
                              rng=jax.random.PRNGKey(0)))
    causal = _config(_tc(0, diffusion_steps=0, mask_token=0))
    with pytest.raises(ValueError, match="'gqa_moe' is not served yet by "
                                         "mesh_spec"):
        _engine(causal, params, mesh_spec=MeshSpec(tp=2))


def test_the_same_block_serves_one_token_after_another(models):
    """``diffusion_block`` 0: the block under the causal mask, through the
    prefill, decode-span and mixed programs every other configuration
    runs, against greedy decoding by the unpaged forward."""
    tc, _, params = models[4]
    causal = _config(_tc(0, diffusion_steps=0, mask_token=0))
    requests = [(_prompt(90 + i, p), n)
                for i, (p, n) in enumerate([(9, 6), (21, 5), (5, 9)])]
    engine = _engine(causal, params)
    results = _serve(engine, requests)
    assert engine.diffusion_rows == 0 and engine.moe_passes > 0
    forward = jax.jit(lambda tokens: transformer_apply(params, tokens,
                                                       causal))
    for (prompt, max_new), result in zip(requests, results):
        tokens = np.zeros((32,), np.int32)  # what follows a row is unseen
        tokens[:len(prompt)] = prompt
        for at in range(len(prompt), len(prompt) + max_new):
            logits = forward(jnp.asarray(tokens)[None])
            tokens[at] = int(jnp.argmax(logits[0, at - 1]))
        assert result.tokens == list(tokens[len(prompt):at + 1])


def test_a_dispatch_says_what_it_carried(models):
    """One ``kubeshare.engine.diffusion`` span a dispatch, the launch
    span's new kinds, the routing span's passes, and the counters' families."""
    tc, config, params = models[4]
    engine = _engine(config, params)
    since = profiling.time.monotonic()
    requests = [(_prompt(95, 8), 8), (_prompt(96, 21), 6)]
    _serve(engine, requests)
    spans = profiling.spans(since=since, name="kubeshare.engine.diffusion")
    total = {key: sum(s[4][key] for s in spans)
             for key in ("lanes", "passes", "commit_passes", "rows",
                         "masked_rows", "committed", "blocks_done",
                         "kv_rows")}
    assert total["committed"] == 14 == engine.tokens_generated
    assert total["passes"] == engine.diffusion_passes["denoise"]
    assert total["commit_passes"] == total["blocks_done"] \
        == engine.diffusion_passes["commit"]
    assert total["rows"] == 4 * total["lanes"] == engine.diffusion_rows
    # r0: a block of 4 passes and its commit pass, then 4 passes and done
    # (no commit pass for a block nothing will read); r1: one known row,
    # so 3 passes + commit, then 3 passes to its sixth token
    assert total["passes"] == 4 + 4 + 3 + 3 and total["commit_passes"] == 2
    assert total["masked_rows"] == 2 * (4 + 3 + 2 + 1) + (3 + 2 + 1) \
        + (4 + 3 + 2)
    assert total["kv_rows"] == 8 * 5 + 12 * 4 + 20 * 4 + 24 * 3
    kinds = {s[4]["kind"] for s in profiling.spans(
        since=since, name="kubeshare.engine.launch")}
    assert kinds <= {"prefill", "diffusion", "mixed_diffusion"}
    assert "diffusion" in kinds
    launches = profiling.spans(since=since, name="kubeshare.engine.launch")
    assert {s[4]["attend"] for s in launches} == {"whole"}
    routed = profiling.spans(since=since, name="kubeshare.engine.routing")
    assert sum(s[4]["passes"] for s in routed) == engine.moe_passes \
        == engine.prefill_chunks + engine.decode_steps
    text = {f.name: f for f in engine.collect_metrics()}
    passes = text["kubeshare_serving_diffusion_passes_total"].samples
    assert {s.labels["kind"]: s.value for s in passes} \
        == {"denoise": 14, "commit": 2}
    for name, value in (("rows", 4 * 16), ("tokens_committed", 14),
                        ("blocks", 2)):
        family = text[f"kubeshare_serving_diffusion_{name}_total"]
        assert [s.value for s in family.samples] == [value]
