"""A mixed dispatch's fused first step against the two entry points back to
back (``paged.paged_mixed_step`` / ``paged.paged_mixed_back_to_back``): the
chunk's rows and the lanes' first rows go through ONE layer loop, and
everything the dispatch hands back is what the composition hands back —
the picks token for token, the pool's written rows, a routed block's
router choices, the logits every pick saw — at ``span`` passes over the
weights instead of ``span + 1``.  And ``weight_passes``, which says so on
the engine's launch spans and in its counter.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models.transformer import (TransformerConfig,
                                              transformer_init)
from kubeshare_tpu.ops.moe import ROUTING_COUNTS
from kubeshare_tpu.serving import (EngineConfig, Request, ServingEngine,
                                   paged)
from kubeshare_tpu.serving.kv_blocks import init_paged_pool
from kubeshare_tpu.utils import profiling

from latent_kinds import (BLOCK, REPO, ROWS, config_of, lane_tables,
                          params_of)

SPAN, CHUNK, LANES = 4, 8, 4
FILLING = 0  # the slot the chunk fills; lane 3 idles
LOGIT_TOLERANCE = 2e-4  # float32: a sum over a batch of another height


def _model(kind: str):
    if kind == "dense":
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
            d_ff=64, max_seq_len=ROWS, positional="rope", dtype=jnp.float32,
            attention="reference")
        return config, transformer_init(jax.random.PRNGKey(0), config)
    if kind == "gqa_moe":
        # the diffusion cell's twin under the causal mask, one token after
        # another: a routed block with a K and a V a head and no state
        with open(os.path.join(REPO, "chipbench", "tests", "configs",
                               "tiny_sdar.json")) as f:
            tc = {**json.load(f)["transformer_config"], "diffusion_block": 0,
                  "diffusion_steps": 0, "mask_token": 0,
                  "dtype": jnp.float32}
        config = TransformerConfig(**tc)
        return config, transformer_init(jax.random.PRNGKey(2), config)
    return (config_of(kind, jnp.float32),
            params_of(kind, 5, jnp.float32))


def _prefilled(config, params, prompts):
    """The pool with every prompt's rows in its lane's pages but the
    filling slot's last chunk, and each decode lane's first token."""
    pool = init_paged_pool(config, 1 + LANES * ROWS // BLOCK, BLOCK)
    pk, pv = pool.k, pool.v
    tables = lane_tables(LANES)
    firsts = np.zeros((LANES,), np.int32)
    for lane, prompt in prompts.items():
        rows = len(prompt) - (CHUNK if lane == FILLING else 0)
        for start in range(0, rows, CHUNK):
            real = prompt[start:min(start + CHUNK, rows)]
            piece = np.zeros((1, CHUNK), np.int32)
            piece[0, :len(real)] = real
            logits, pk, pv, *_ = paged.paged_prefill_step(
                params, config, pk, pv, tables[lane][None],
                jnp.asarray([start]), jnp.ones((1,), bool),
                jnp.asarray(piece), jnp.asarray([len(real) - 1]))
        firsts[lane] = int(np.argmax(np.asarray(logits[0])))
    return pk, pv, tables, firsts


def _run(step, config, params, pk, pv, tables, prompts, firsts, temps, eos,
         budgets):
    """One mixed dispatch by ``step``: the filling slot's last chunk (its
    last real row short of the chunk's end) beside lanes 1 and 2."""
    seen = []

    def pick(logits, temps, keys):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits,
                           ordered=True)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.vmap(jax.random.categorical)(
            keys, logits / jnp.where(temps > 0, temps, 1.0)[:, None])
        return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)

    prompt = prompts[FILLING]
    start = len(prompt) - CHUNK
    lengths = np.asarray([len(prompts.get(lane, ())) for lane in
                          range(LANES)], np.int32)
    lengths[FILLING] = 0
    active = np.asarray([lane != FILLING and lane in prompts
                         for lane in range(LANES)])
    keys = jax.random.split(jax.random.PRNGKey(7), LANES * SPAN).reshape(
        LANES, SPAN, 2)
    out = jax.jit(lambda pk, pv: step(
        params, config, pick, SPAN, eos, pk, pv, tables[FILLING][None],
        jnp.asarray([start]), jnp.asarray(prompt[start:][None]),
        jnp.asarray([CHUNK - 3]), jnp.asarray([temps[FILLING]]),
        jax.random.PRNGKey(3)[None], tables, jnp.asarray(lengths),
        jnp.asarray(active), jnp.asarray(firsts),
        jnp.asarray(temps, jnp.float32), keys, jnp.asarray(budgets),
        routing=config.routed))(pk, pv)
    jax.effects_barrier()
    return [np.asarray(o) for o in out], seen


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", ["dense", "latent_shortcut", "latent_moe",
                                  "gqa_moe"])
def test_fused_first_step_is_the_back_to_back_composition(kind, sampled):
    config, params = _model(kind)
    rng = np.random.default_rng(11)
    prompts = {FILLING: rng.integers(0, 64, 3 * CHUNK).astype(np.int32),
               1: rng.integers(0, 64, 13).astype(np.int32),
               2: rng.integers(0, 64, 22).astype(np.int32)}
    pk, pv, tables, firsts = _prefilled(config, params, prompts)
    temps = [0.9, 0.8, 1.1, 0.0] if sampled else [0.0] * LANES
    # lane 2's budget ends inside the span
    budgets = np.asarray([0, 9, 2, 0], np.int32)

    def both(eos):
        return [_run(step, config, params, pk, pv, tables, prompts, firsts,
                     temps, eos, budgets)
                for step in (paged.paged_mixed_step,
                             paged.paged_mixed_back_to_back)]

    # ... and lane 1 meets EOS at the span's second step
    (free, _), _ = both(None)
    eos = int(free[1][1, 1])
    (fused, fused_saw), (split, split_saw) = both(eos)

    p_picked, emitted, fused_k, fused_v, *fused_counts = fused
    np.testing.assert_array_equal(p_picked, split[0])
    np.testing.assert_array_equal(emitted, split[1])
    assert emitted[1, 1] == eos
    # block 0 is the scratch block: idle and finished lanes' rows land there
    for ours, theirs, before in ((fused_k, split[2], pk),
                                 (fused_v, split[3], pv)):
        np.testing.assert_allclose(ours[:, 1:], theirs[:, 1:], atol=1e-5)
        written = np.any(ours != np.asarray(before), axis=(0, 2, 3, 4))
        np.testing.assert_array_equal(
            written[1:], np.any(theirs != np.asarray(before),
                                axis=(0, 2, 3, 4))[1:])
    # every pick saw the logits the composition's saw: the chunk's, then a
    # step's lanes'
    assert [s.shape for s in fused_saw] == [s.shape for s in split_saw]
    assert len(fused_saw) == 1 + SPAN
    for ours, theirs in zip(fused_saw, split_saw):
        live = slice(1, 3) if ours.shape[0] == LANES else slice(None)
        assert np.abs(ours[live] - theirs[live]).max() < LOGIT_TOLERANCE
    if config.routed:
        ours, theirs = fused_counts[0], split[4]
        for name in ("held", "zero", "absent"):
            at = ROUTING_COUNTS.index(name)
            assert ours[at] == theirs[at], name
        assert ours[-1] == theirs[-1]  # the rows that chose
        # one grouping over both groups' rows: no more experts read
        touched = ROUTING_COUNTS.index("touched")
        assert ours[touched] <= theirs[touched]


STATEFUL = {
    "retention": {
        "vocab_size": 512, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "n_layers": 2, "d_ff": 128, "max_seq_len": 512,
        "positional": "rope", "block": "retention", "head_width": 16},
    "conv": {
        "vocab_size": 512, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "n_layers": 3, "d_ff": 128, "max_seq_len": 256,
        "positional": "rope", "block": "gqa_moe", "head_width": 64,
        "n_routed_experts": 8, "router_top_k": 2, "expert_d_ff": 32,
        "first_dense_layers": 1, "conv_taps": 3,
        "layer_operators": ("conv", "attention", "conv")},
}


@pytest.mark.parametrize("family,passes", [("dense", SPAN),
                                           ("retention", SPAN + 1),
                                           ("conv", SPAN + 1)])
def test_weight_passes_on_the_launch_span_and_in_the_counter(
        family, passes, monkeypatch):
    """``weight_passes``: the passes over the layer stack a dispatch's
    program makes — a mixed dispatch's ``decode_span`` where the chunk rides
    the first, ``decode_span + 1`` where a state by slot keeps the chunk a
    pass of its own; a decode dispatch's ``decode_span``, a prefill
    chunk's 1."""
    monkeypatch.setattr(paged, "KEY_BLOCK", 32)  # a tail of 5 pages, not 65
    if family == "dense":
        config = _model("dense")[0]
    else:
        config = TransformerConfig(**STATEFUL[family], dtype=jnp.float32)
    params = transformer_init(jax.random.PRNGKey(0), config)
    engine = ServingEngine(params, config, EngineConfig(
        num_slots=3, block_size=8, num_blocks=49, max_request_len=64,
        prefill_chunk=8, decode_span=SPAN))
    rng = np.random.default_rng(3)
    since = time.monotonic()
    engine.submit(Request("a", rng.integers(0, 64, 5), 12))
    engine.step()  # a's prompt: a prefill chunk alone
    engine.submit(Request("b", rng.integers(0, 64, 20), 3))
    engine.run()
    launches = [r[4] for r in profiling.spans(
        since=since, name="kubeshare.engine.launch")]
    by_kind = {kind: [a["weight_passes"] for a in launches
                      if a["kind"] == kind]
               for kind in ("mixed", "decode", "prefill")}
    assert all(by_kind.values())
    assert set(by_kind["mixed"]) == {passes}
    assert set(by_kind["decode"]) == {SPAN}
    assert set(by_kind["prefill"]) == {1}
    assert engine.weight_passes == {k: sum(v) for k, v in by_kind.items()}
    families = {f.name: f for f in engine.collect_metrics()}
    samples = families["kubeshare_serving_weight_passes_total"].samples
    assert {s.labels["kind"]: s.value for s in samples} \
        == engine.weight_passes
