"""The 'retention' block (power-retention layers, ``ops/retention.py``): a
recurrent state a lane beside a short paged tail of keys and values, folded
a key block at a time, held to the plain reference
(``chipbench/brumby_14b_base_reference.py``: the QUADRATIC form, which never
forms a state) at a small size on the CPU: d 64, head width 16 (136 features
of ``phi`` in 144 columns), 2 KV heads, seeded weights.  The fold length is
``paged.KEY_BLOCK``, patched to 32 here as ``tests/test_key_blocks.py``
patches it to 8: no knob is added for the tests.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import brumby_14b_base_reference as reference  # noqa: E402
from chipbench import brumby_14b_base_roofline as counts  # noqa: E402
from chipbench import brumby_14b_base_weights as weights  # noqa: E402
from kubeshare_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, transformer_apply, transformer_init)
from kubeshare_tpu.ops import retention  # noqa: E402
from kubeshare_tpu.parallel.mesh import MeshSpec  # noqa: E402
from kubeshare_tpu.serving import (  # noqa: E402
    QOS_OPPORTUNISTIC, EngineConfig, Request, ServingEngine, TenantRegistry,
    TenantSpec, paged, stages)
from kubeshare_tpu.serving.kv_blocks import (  # noqa: E402
    init_paged_pool, init_retention_states, kv_row_layout)
from kubeshare_tpu.utils import profiling  # noqa: E402

KEY_BLOCK = 32
TC = {"vocab_size": 512, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
      "n_layers": 2, "d_ff": 128, "max_seq_len": 512, "positional": "rope",
      "dtype": "float32", "block": "retention", "head_width": 16,
      "rope_theta": 1000000.0, "norm_eps": 1e-06}
# float32 end to end: what is left between the state form and the quadratic
# form is the order of the sums (a fold adds a key block at once, the
# reference a row block at once), 3e-6 on logits of size 4 here
LOGIT_TOLERANCE = 1e-5 * 4
# bf16 weights and rows: the program rounds q, k, v, the tail's weights and
# every matrix product's inputs to 8 bits of mantissa where the reference
# keeps 24; over two layers that moves a logit of size 4 by 0.01-0.05
# (measured here: 0.06 at most over the cases below), and an arithmetic with
# another bit less (fp8's e4m3 has 3) moves it by tenths
BF16_LOGIT_TOLERANCE = 0.15


def _config(**changes) -> TransformerConfig:
    tc = {**TC, **changes}
    return TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})


@pytest.fixture(autouse=True)
def short_key_block(monkeypatch):
    monkeypatch.setattr(paged, "KEY_BLOCK", KEY_BLOCK)
    monkeypatch.setattr(reference, "PAD_TO", 64)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)


@pytest.fixture(scope="module")
def model():
    return TC, _config(), weights.make_weights(11, TC)


def _engine(config, params, **changes) -> ServingEngine:
    kwargs = dict(num_slots=3, block_size=8, num_blocks=1 + 3 * 8 + 2,
                  max_request_len=512, prefill_chunk=32, decode_span=4)
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
# the mathematics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hd", [2, 16, 128])
def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k(hd):
    q = jax.random.normal(jax.random.PRNGKey(0), (7, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (7, hd))
    features = retention.phi(q)
    assert features.shape == (7, retention.phi_width(hd)) \
        == (7, (hd // 2 + 1) * hd)
    # hd (hd + 1) / 2 features; the other columns are zeros for good
    assert int(np.count_nonzero(np.asarray(
        retention.phi(jnp.ones((hd,)))))) == hd * (hd + 1) // 2
    np.testing.assert_allclose(
        np.asarray(jnp.sum(features * retention.phi(k), -1)),
        np.asarray(jnp.sum(q * k, -1) ** 2), rtol=2e-5, atol=1e-5)


def test_the_state_form_is_the_quadratic_form_over_three_folds():
    """130 rows a lane: three key blocks of 32 folded one after another,
    each query from its lane's state as of the fold and the rows since —
    against every row against every earlier row, in float32 to 1e-5."""
    b, h, h_kv, hd, n = 2, 4, 2, 16, 130
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(keys[0], (b, h, n, hd))
    k = jax.random.normal(keys[1], (b, h_kv, n, hd))
    v = jax.random.normal(keys[2], (b, h_kv, n, hd))
    a = jax.nn.log_sigmoid(3.0 + jax.random.normal(keys[3], (b, n, h_kv)))
    whole = retention.retention_quadratic(q, k, v, a, jnp.float32)
    state = 7.0 + jnp.zeros((b, h_kv, retention.state_rows(hd),
                             retention.phi_width(hd)))  # a slot's leavings
    for folded in range(0, n, KEY_BLOCK):
        rows = slice(folded, min(folded + KEY_BLOCK, n))
        span = rows.stop - folded
        cum = jnp.cumsum(a[:, rows], axis=1)
        row = jnp.broadcast_to(jnp.arange(span)[None], (b, span))
        tail = retention.tail_sums(q[:, :, rows], k[:, :, rows],
                                   v[:, :, rows], cum, cum, row,
                                   jnp.float32)
        held = retention.state_sums(
            q[:, :, rows], state, cum, jnp.full((b,), folded > 0))
        out = retention.retention_output(tail, held, jnp.float32)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(whole[:, :, rows]),
                                   rtol=1e-5, atol=1e-5)
        if span == KEY_BLOCK:
            state = jnp.stack([retention.fold_update(
                state[i], k[i, :, rows], v[i, :, rows], a[i, rows],
                folded > 0) for i in range(b)])
    assert folded == 128  # three folds, and a tail of two rows after them


def test_the_block_has_the_published_shape(model):
    tc, config, params = model
    init = transformer_init(jax.random.PRNGKey(0), config)
    assert jax.tree.structure(init) == jax.tree.structure(params)
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, params)
    layer = params["layers"][0]
    assert set(layer) == {"attn", "norm1", "norm2", "ffn"}
    attn = layer["attn"]
    # the input projections as matrices, a gate WITH a bias a KV head
    assert attn["wq"].shape == (64, 4 * 16)
    assert attn["wk"].shape == attn["wv"].shape == (64, 2 * 16)
    assert attn["wo"].shape == (4, 16, 64)
    assert attn["gate"]["w"].shape == (64, 2)
    assert attn["gate"]["b"].shape == (2,)
    assert not config.routed and not config.latent
    layout = kv_row_layout(config)
    assert (layout.kind, layout.gate_heads) == ("kv_heads", 2)
    pool = init_paged_pool(config, 5, 8)
    assert pool.k.shape == pool.v.shape == (2, 5, 2, 8, 16)
    assert pool.gate.shape == (2, 2, 5 * 8) and pool.gate.dtype == jnp.float32
    # what a token caches, and what the harness sizes the pool by
    assert pool.bytes_per_block() == 8 * counts.kv_bytes_per_row(tc) \
        == 8 * 2 * 2 * (2 * 16 * 4 + 4)
    states = init_retention_states(config, 3)
    assert len(states) == 2 and states[0].shape == (3, 2, 24, 144)
    assert sum(s.nbytes for s in states) == 3 * counts.state_bytes_per_lane(tc)


@pytest.mark.parametrize("changes,said", [
    (dict(moe_every=2), "takes neither moe_every"),
    (dict(positional="learned"), "positional='rope'"),
    (dict(head_width=15), "head_width must be even"),
    (dict(n_kv_heads=3), "multiple of n_kv_heads"),
    (dict(diffusion_block=4), "are block 'gqa_moe''s"),
    (dict(d_ff=0), "d_ff >= 1"),
])
def test_a_configuration_that_makes_no_sense_is_refused(changes, said):
    with pytest.raises(ValueError, match=said):
        _config(**changes)


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
    the paged programs, a lane in slot 1 of 3 whose state holds another
    request's leavings; and how far it had folded at the end."""
    n = len(tokens)
    pool = init_paged_pool(config, 2 + -(-n // bs), bs)
    recurrent = paged.Recurrent(pool.gate, tuple(
        s + 7.0 for s in init_retention_states(config, 3)))
    pk, pv = pool.k, pool.v
    width = 512 // bs
    table = np.zeros((width,), np.int32)
    table[: n // bs + 1] = np.arange(1, n // bs + 2)
    slot, folded, at, got = 1, 0, 0, {}
    prefill = jax.jit(lambda pk, pv, rec, seg, start, last, fo:
                      paged.paged_prefill_step(
                          params, config, pk, pv, jnp.asarray(table[None]),
                          start, jnp.ones((1,), bool), seg, last,
                          recurrent=rec, folded=fo,
                          slots=jnp.asarray([slot])))
    while at < prompt_len:
        rows = min(chunk, prompt_len - at)
        segment = np.zeros((1, chunk), np.int32)
        segment[0, :rows] = tokens[at:at + rows]
        logits, pk, pv, recurrent = prefill(
            pk, pv, recurrent, jnp.asarray(segment), jnp.asarray([at]),
            jnp.asarray([rows - 1]), jnp.asarray([folded]))
        at += rows
        folded += KEY_BLOCK * (at - folded >= KEY_BLOCK)
        got[at - 1] = np.asarray(logits[0])
    tables = np.zeros((3, width), np.int32)
    tables[slot] = table
    active = jnp.asarray([False, True, False])
    step = jax.jit(lambda pk, pv, rec, lens, toks, fo:
                   paged.paged_decode_step(
                       params, config, pk, pv, jnp.asarray(tables), lens,
                       active, toks, recurrent=rec, folded=fo))
    fold = jax.jit(lambda pk, pv, rec, lens, fo: paged.fold_lanes(
        pk, pv, rec, jnp.asarray(tables), fo, lens, active))
    for at in range(prompt_len, n):
        lane = lambda value: jnp.zeros((3,), jnp.int32).at[slot].set(value)
        logits, pk, pv, recurrent = step(pk, pv, recurrent, lane(at),
                                         lane(int(tokens[at])), lane(folded))
        got[at] = np.asarray(logits[slot])
        recurrent = fold(pk, pv, recurrent, lane(at + 1), lane(folded))
        folded += KEY_BLOCK * (at + 1 - folded >= KEY_BLOCK)
    return got, folded


def test_prefill_in_chunks_then_decode_gives_the_references_logits(model):
    """A prompt of 100 rows in chunks of 32 (three folds while it
    prefills), then 50 decode steps (a fourth fold, at row 128), in float32:
    every logit the programs give is the quadratic form's."""
    tc, config, params = model
    tokens = _prompt(4, 150)
    got, folded = _served_logits(config, params, tokens, 100)
    assert folded == 128 and len(got) == 4 + 50
    rows = np.asarray(sorted(got))
    theirs = reference.reference_logits(params, tc, tokens, rows)
    worst = max(np.abs(got[r] - theirs[i]).max()
                for i, r in enumerate(rows))
    assert worst < LOGIT_TOLERANCE, worst


def test_served_in_bf16_the_logits_stay_within_the_stated_tolerance():
    """The same in the precision the cell states: bf16 weights and rows,
    the state and every sum that feeds it in float32."""
    tc = {**TC, "dtype": "bfloat16"}
    config, params = _config(dtype="bfloat16"), weights.make_weights(12, tc)
    assert params["embed"].dtype == jnp.bfloat16
    tokens = _prompt(6, 120)
    got, folded = _served_logits(config, params, tokens, 90)
    assert folded == 96
    rows = np.asarray(sorted(got))
    theirs = reference.reference_logits(params, tc, tokens, rows)
    worst = max(np.abs(got[r] - theirs[i]).max()
                for i, r in enumerate(rows))
    assert 1e-4 < worst < BF16_LOGIT_TOLERANCE, worst


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

REQUESTS = [(100, 40), (250, 6), (7, 70), (33, 31), (64, 64)]


def test_the_engine_serves_what_the_reference_puts_first(model):
    """Five requests through ``submit`` / ``run`` on three slots — prompts
    under a key block and of eight, a chunk that ends on a key block's last
    row, folds while prefilling and while decoding, in mixed dispatches: in
    float32 every served token is the reference's best."""
    tc, config, params = model
    engine = _engine(config, params)
    engine.warmup()
    warm = engine.compile_counts()
    assert warm["prefill"] == warm["mixed"] == 3  # buckets 8, 16, 32
    assert engine.prefix_index is None  # a match could give nothing
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
    # every key block completed was folded, and everything handed back
    folds = sum((p + n - 1) // KEY_BLOCK for p, n in REQUESTS)
    assert engine.retention_folds == folds == 17
    assert engine.allocator.blocks_in_use == 0
    assert engine.allocator.free_blocks == 26
    assert engine.retention_state_reads > 0 < engine.retention_tail_rows
    spans = [r for r in profiling.spans(since=since,
                                        name="kubeshare.engine.retention")]
    assert spans and all(
        set(r[4]) == {"lanes", "state_lanes", "passes", "state_reads",
                      "tail_rows", "folds", "folded_rows", "pages_freed",
                      "chunk"} for r in spans)
    assert sum(r[4]["folds"] for r in spans) == folds
    assert sum(r[4]["folded_rows"] for r in spans) == folds * KEY_BLOCK
    assert sum(r[4]["state_reads"] for r in spans) \
        == engine.retention_state_reads
    names = {f.name for f in engine.collect_metrics()}
    assert {f"kubeshare_serving_retention_{kind}_total" for kind in
            ("state_reads", "tail_rows", "folds", "pages_freed")} <= names


def test_a_pool_of_two_key_blocks_a_lane_serves_eight(model):
    """A request is funded by its tail, not by its length: two lanes, 8
    pages of 8 rows each (two key blocks of 32) and not one page more, serve
    requests of 256 rows — eight key blocks — side by side; the pages behind
    every fold are free again at once."""
    tc, config, params = model
    engine = _engine(config, params, num_slots=2, num_blocks=1 + 2 * 8)
    engine.warmup()
    prompts = [_prompt(31, 200), _prompt(32, 90)]
    results = [engine.submit(Request("long", prompts[0], 56)),
               engine.submit(Request("grows", prompts[1], 166))]
    most = 0
    while engine.step():
        most = max(most, engine.allocator.blocks_in_use)
        for slot in engine._slots:
            if slot.state != "free":
                # a lane holds its unfolded rows' pages and no page behind
                assert len(slot.blocks) <= 8
                assert not slot.table[: slot.folded // 8].any()
    assert most == 16 and engine.allocator.blocks_in_use == 0
    assert engine.retention_folds == 7 + 7
    # behind 7 folds a lane hands 28 pages back and draws the 24 its 256
    # rows still lack of the 8 it was admitted with: 4 fewer at the end
    assert engine.retention_pages_freed == 2 * (28 - 24)
    for prompt, result in zip(prompts, results):
        assert _gaps(params, tc, prompt, result.tokens).max() == 0.0
    with pytest.raises(ValueError, match="over max_request_len"):
        engine.submit(Request("far", _prompt(33, 500), 20))


def test_a_preempted_request_serves_the_tokens_it_would_have(model):
    """A Guarantee admission with no free slot preempts the lane after it
    has folded; its state is dropped with its pages, and the resumed
    request prefills prompt + generated from row 0, folds its way back and
    serves the unpreempted stream, no token twice."""
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
    while engine.tokens_generated < 30:  # past row 96: three folds in
        assert engine.step()
    assert engine._slots[0].folded == 96 and not victim.done
    served = engine.submit(Request("gold", gold, 9, tenant="gold"))
    engine.run()
    assert engine.preemptions == {"batch": 1}
    assert victim.tokens == expected.tokens and len(victim.tokens) == 60
    assert _gaps(params, tc, gold, served.tokens).max() == 0.0
    assert engine.tokens_generated == 60 + 9
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
    (dict(block_size=12), "must divide it"),
    (dict(prefill_chunk=64), "may not exceed it"),
    (dict(decode_span=64), "may not exceed it"),
])
def test_what_cannot_carry_a_state_is_refused(model, changes, said):
    _, config, params = model
    with pytest.raises(ValueError, match=said) as refused:
        _engine(config, params, **changes)
    assert "'retention'" in str(refused.value)


def test_a_shared_host_tier_is_refused(model):
    from kubeshare_tpu.serving.kv_tier import HostTier, LRUTierPolicy

    _, config, params = model
    with pytest.raises(ValueError, match="a shared host tier"):
        ServingEngine(params, config, EngineConfig(
            num_slots=2, block_size=8, num_blocks=20, max_request_len=128,
            prefill_chunk=32), shared_host_tier=HostTier(
                1 << 20, LRUTierPolicy()))


def test_the_dense_cache_decoder_refuses_the_block(model):
    from kubeshare_tpu.models.decoding import init_kv_cache

    with pytest.raises(ValueError, match="no dense-cache decoder"):
        init_kv_cache(model[1], 1)


# ---------------------------------------------------------------------------
# the stage table
# ---------------------------------------------------------------------------

def test_the_mechanisms_scopes_are_one_stage(model):
    """``retention_state``, ``retention_tail``, ``retention_fold``, ``gate``
    and ``phi`` are ONE stage; the q/k norms stay the attention's, the
    tail's row writes ``kv_write``'s, the SwiGLU ``ffn``'s."""
    assert {scope for scope, stage in stages.STAGE_OF_SCOPE.items()
            if stage == "retention"} == {
        "retention_state", "retention_tail", "retention_fold", "gate",
        "phi"}
    assert stages.STAGES[-3:] == ("retention", "conv", "unscoped")
    assert stages.stage_of("jit(f)/attention/qk_norm/mul") == "attention"
    _, config, params = model
    engine = _engine(config, params)
    engine.warmup()
    table = stages.stage_table(stages.program_name("mixed", 32))
    assert {"retention", "attention", "kv_write", "ffn", "head"} \
        <= set(table.values())
