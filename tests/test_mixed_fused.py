"""A mixed dispatch's fused first step against the two entry points back to
back (``paged.paged_mixed_step`` / ``paged.paged_mixed_back_to_back``): the
chunk's rows and the lanes' first rows go through ONE layer loop, and
everything the dispatch hands back is what the composition hands back —
the picks token for token, the pool's written rows, a routed block's
router choices, the logits every pick saw — at ``span`` passes over the
weights instead of ``span + 1``.  A model with a state BY SLOT (retention
layers, short convolutions) rides the same step: its gate columns and every
slot's state are the composition's too.  And ``weight_passes``, which says
so on the engine's launch spans and in its counter.
"""

import functools
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
from kubeshare_tpu.serving.kv_blocks import (init_conv_states,
                                             init_paged_pool,
                                             init_retention_states)
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


_saw = []  # the logits every pick of the running program saw


def _pick(logits, temps, keys):
    jax.debug.callback(lambda x: _saw.append(np.asarray(x)), logits,
                       ordered=True)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = jax.vmap(jax.random.categorical)(
        keys, logits / jnp.where(temps > 0, temps, 1.0)[:, None])
    return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)


def _same_pool_rows(fused, split, before):
    """(K, V) of the fused step against the composition's: the same rows
    written, to the same values.  Block 0 is the scratch block: idle and
    finished lanes' rows, and a chunk's padding, land there."""
    for ours, theirs, was in zip(fused, split, before):
        np.testing.assert_allclose(ours[:, 1:], theirs[:, 1:], atol=1e-5)
        written = np.any(ours != np.asarray(was), axis=(0, 2, 3, 4))
        np.testing.assert_array_equal(
            written[1:], np.any(theirs != np.asarray(was),
                                axis=(0, 2, 3, 4))[1:])


def _same_picks_seen(fused_saw, split_saw, lanes, live):
    """Every pick saw the logits the composition's saw: the chunk's, then a
    step's lanes' (compared on the ``live`` lanes)."""
    assert [s.shape for s in fused_saw] == [s.shape for s in split_saw]
    assert len(fused_saw) == 1 + SPAN
    for ours, theirs in zip(fused_saw, split_saw):
        rows = live if ours.shape[0] == lanes else slice(None)
        assert np.abs(ours[rows] - theirs[rows]).max() < LOGIT_TOLERANCE


def _same_routing(ours, theirs):
    for name in ("held", "zero", "absent"):
        at = ROUTING_COUNTS.index(name)
        assert ours[at] == theirs[at], name
    assert ours[-1] == theirs[-1]  # the rows that chose
    # one grouping over both groups' rows: no more experts read
    touched = ROUTING_COUNTS.index("touched")
    assert ours[touched] <= theirs[touched]


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
    del _saw[:]
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
        params, config, _pick, SPAN, eos, pk, pv, tables[FILLING][None],
        jnp.asarray([start]), jnp.asarray(prompt[start:][None]),
        jnp.asarray([CHUNK - 3]), jnp.asarray([temps[FILLING]]),
        jax.random.PRNGKey(3)[None], tables, jnp.asarray(lengths),
        jnp.asarray(active), jnp.asarray(firsts),
        jnp.asarray(temps, jnp.float32), keys, jnp.asarray(budgets),
        routing=config.routed))(pk, pv)
    jax.effects_barrier()
    return [np.asarray(o) for o in out], list(_saw)


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", ["dense", "latent_shortcut", "latent_moe",
                                  "gqa_moe", "conv", "retention"])
def test_fused_first_step_is_the_back_to_back_composition(kind, sampled,
                                                          monkeypatch):
    if kind in STATEFUL:
        # a key block of 4 pages, so that prompts of 30-64 rows fold
        monkeypatch.setattr(paged, "KEY_BLOCK", S_KEY_BLOCK)
        for chunk in S_CHUNKS:
            _stateful_case(kind, sampled, chunk)
        return
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
    _same_pool_rows((fused_k, fused_v), split[2:4], (pk, pv))
    _same_picks_seen(fused_saw, split_saw, LANES, slice(1, 3))
    if config.routed:
        _same_routing(fused_counts[0], split[4])


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


# the stateful cases' sizes: 6 slots (slot 0 fills), pages of 8 rows, a key
# block of 32, a table of 16 pages a lane
S_LANES, S_PAGE, S_KEY_BLOCK, S_TABLE = 6, 8, 32, 16
# lane -> (rows it holds going in, its budget): lane 1 runs the whole span
# and its fold falls due inside it (a tail of 30 rows, a state already); lane
# 2's ONE emission completes its first key block, so it folds though it left
# the span at step 0; lane 3's budget ends inside the span, on the row that
# completes a key block; lane 4 meets EOS at the span's second step; lane 5
# idles beside a stale state
S_HELD = {1: (S_KEY_BLOCK + 30, 9), 2: (S_KEY_BLOCK - 1, 1),
          3: (S_KEY_BLOCK - 2, 2), 4: (13, 9)}
# the chunk -> (rows the filling slot holds going in, the chunk's last real
# row): "mid" starts mid-prompt from the slot's state and completes a key
# block (a fold onto a state); "row0" starts at row 0 beside a slot that
# holds a stale state, and pads forward
S_CHUNKS = {"mid": (2 * S_KEY_BLOCK - CHUNK, CHUNK - 1),
            "row0": (0, CHUNK - 3)}

@functools.lru_cache(maxsize=None)
def _stateful_programs(kind: str):
    """The model and its three programs, each traced once for every case:
    the chunk alone (what fills the lanes), and the mixed dispatch fused and
    back to back — EOS rides as an argument, not a constant."""
    config = TransformerConfig(**STATEFUL[kind], dtype=jnp.float32)
    params = transformer_init(jax.random.PRNGKey(0), config)
    prefill = jax.jit(lambda pk, pv, rec, table, start, tokens, last, folded,
                      slot: paged.paged_prefill_step(
                          params, config, pk, pv, table, start,
                          jnp.ones((1,), bool), tokens, last, recurrent=rec,
                          folded=folded, slots=slot))

    def mixed(step):
        return jax.jit(lambda pk, pv, rec, eos, p_folded, p_slot, d_folded,
                       *rest: step(
                           params, config, _pick, SPAN, eos, pk, pv, *rest,
                           routing=config.routed, recurrent=rec,
                           p_folded=p_folded, p_slot=p_slot,
                           d_folded=d_folded))

    return (config, prefill, mixed(paged.paged_mixed_step),
            mixed(paged.paged_mixed_back_to_back))


def _stateful_case(kind: str, sampled: bool, chunk: str):
    config, prefill, fused_step, split_step = _stateful_programs(kind)
    retention = config.block == "retention"
    rng = np.random.default_rng(12)
    pk, pv, *gate = init_paged_pool(config, 1 + S_LANES * S_TABLE,
                                    S_PAGE).arrays()
    tables = jnp.asarray(1 + np.arange(S_LANES * S_TABLE).reshape(
        S_LANES, S_TABLE), jnp.int32)
    # every slot starts out holding what an earlier request left there
    states = (init_retention_states if retention else init_conv_states)(
        config, S_LANES)
    rec = paged.Recurrent(gate[0] if retention else None, tuple(
        jnp.asarray(rng.normal(size=a.shape), a.dtype) for a in states))
    held, last_row = S_CHUNKS[chunk]
    rows = {FILLING: held, **{lane: n for lane, (n, _) in S_HELD.items()}}
    folded = np.zeros((S_LANES,), np.int32)
    firsts = np.zeros((S_LANES,), np.int32)
    prompts = {lane: rng.integers(0, 512, n + CHUNK).astype(np.int32)
               for lane, n in rows.items()}
    for lane, n in rows.items():
        for start in range(0, n, CHUNK):
            real = prompts[lane][start:min(start + CHUNK, n)]
            piece = np.zeros((1, CHUNK), np.int32)
            piece[0, :len(real)] = real
            logits, pk, pv, rec = prefill(
                pk, pv, rec, tables[lane][None], jnp.asarray([start]),
                jnp.asarray(piece), jnp.asarray([len(real) - 1]),
                jnp.asarray(folded[lane:lane + 1]), jnp.asarray([lane]))
            if retention and start + len(real) - folded[lane] >= S_KEY_BLOCK:
                folded[lane] += S_KEY_BLOCK  # as the engine's .consume does
            firsts[lane] = int(np.argmax(np.asarray(logits[0])))
    assert not retention or list(folded) == [
        held // S_KEY_BLOCK * S_KEY_BLOCK, S_KEY_BLOCK, 0, 0, 0, 0]

    temps = np.asarray([0.9, 0.8, 1.1, 0.7, 1.2, 0.0] if sampled
                       else [0.0] * S_LANES, np.float32)
    lengths = np.asarray([rows.get(lane, 0) * (lane != FILLING)
                          for lane in range(S_LANES)], np.int32)
    active = np.asarray([lane in S_HELD for lane in range(S_LANES)])
    budgets = np.asarray([S_HELD.get(lane, (0, 0))[1]
                          for lane in range(S_LANES)], np.int32)
    keys = jax.random.split(jax.random.PRNGKey(7), S_LANES * SPAN).reshape(
        S_LANES, SPAN, 2)
    firsts[FILLING] = 0

    def run(step, eos):
        del _saw[:]
        out = step(
            pk, pv, rec, jnp.asarray(eos, jnp.int32),
            jnp.asarray(folded[:1]), jnp.asarray([FILLING]),
            jnp.asarray(folded), tables[FILLING][None], jnp.asarray([held]),
            jnp.asarray(prompts[FILLING][held:][None]),
            jnp.asarray([last_row]), jnp.asarray(temps[:1]),
            jax.random.PRNGKey(3)[None], tables, jnp.asarray(lengths),
            jnp.asarray(active), jnp.asarray(firsts), jnp.asarray(temps),
            keys, jnp.asarray(budgets))
        jax.effects_barrier()
        *arrays, after = out
        return [np.asarray(o) for o in arrays], after, list(_saw)

    # lane 4 meets EOS at the span's second step
    free = run(split_step, -1)[0][1]
    eos = int(free[1, 4])
    assert eos not in free[:, 1:4]  # and no other lane does
    (fused, ours, fused_saw), (split, theirs, split_saw) = (
        run(fused_step, eos), run(split_step, eos))
    np.testing.assert_array_equal(fused[0], split[0])  # the chunk's pick
    np.testing.assert_array_equal(fused[1], split[1])  # the lanes' tokens
    assert fused[1][1, 4] == eos
    _same_pool_rows(fused[2:4], split[2:4], (pk, pv))
    _same_picks_seen(fused_saw, split_saw, S_LANES, slice(1, 5))
    if config.routed:
        _same_routing(fused[4], split[4])
    # every slot's state, and every gate column outside the scratch block
    if retention:
        np.testing.assert_allclose(np.asarray(ours.gate)[:, :, S_PAGE:],
                                   np.asarray(theirs.gate)[:, :, S_PAGE:],
                                   atol=1e-6)
    changed = set()
    for mine, other, before in zip(ours.states, theirs.states, rec.states):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(other),
                                   rtol=1e-4, atol=1e-5)
        changed |= {slot for slot in range(S_LANES) if not np.array_equal(
            np.asarray(mine[slot]), np.asarray(before[slot]))}
    # a fold where one fell due and nowhere else; a convolution's state
    # moves with every live row; the idle lane's is as it was
    due = {1, 2, 3} | ({FILLING} if chunk == "mid" else set())
    assert changed == (due if retention else {0, 1, 2, 3, 4})


@pytest.mark.parametrize("family,passes", [("dense", SPAN),
                                           ("retention", SPAN),
                                           ("conv", SPAN)])
def test_weight_passes_on_the_launch_span_and_in_the_counter(
        family, passes, monkeypatch):
    """``weight_passes``: the passes over the layer stack a dispatch's
    program makes — a mixed dispatch's ``decode_span`` where the chunk rides
    the first, ``decode_span + 1`` where the two parts' composition keeps it a
    pass of its own (the sharded context alone: a model with a state by
    slot rides the first pass too); a decode dispatch's ``decode_span``, a
    prefill chunk's 1."""
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
