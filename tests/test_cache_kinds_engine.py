"""The engine's side of a cache BY LAYER KIND (``test_cache_kinds.py`` holds
the model and the step programs): an allocator and a table a kind, the window
kind's pages reserved up front and handed back behind the window after every
dispatch, through ``submit`` / ``run`` against the plain reference
(``chipbench/smallthinker_21ba3b_reference.py``) on requests that cross a
20-row window many times over pages of 8 — at a small size on the CPU, in
float32, engines of one tiny configuration (the compile cache serves them).
"""

import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import smallthinker_21ba3b_reference as reference  # noqa: E402
from chipbench import smallthinker_21ba3b_weights as weights  # noqa: E402
from kubeshare_tpu.models.transformer import TransformerConfig  # noqa: E402
from kubeshare_tpu.parallel.mesh import MeshSpec  # noqa: E402
from kubeshare_tpu.serving import (  # noqa: E402
    QOS_OPPORTUNISTIC, EngineConfig, Request, ServingEngine, TenantRegistry,
    TenantSpec, stages)
from kubeshare_tpu.serving.kv_blocks import BlockExhausted  # noqa: E402
from kubeshare_tpu.utils import profiling  # noqa: E402

WINDOW, PAGE = 20, 8
TC = {"vocab_size": 512, "d_model": 64, "n_heads": 14, "n_kv_heads": 2,
      "n_layers": 4, "d_ff": 0, "max_seq_len": 1024, "positional": "rope",
      "dtype": "float32", "block": "gqa_moe", "head_width": 16,
      "rope_theta": 1500000.0, "norm_eps": 1e-06, "n_routed_experts": 8,
      "router_top_k": 3, "routed_scaling_factor": 1.0,
      "router_scoring": "softmax", "router_renormalise": True,
      "expert_d_ff": 32,
      "layer_operators": ["global", "window", "window", "window"],
      "attention_window": WINDOW, "qk_norm": False,
      "router_input": "layer_input", "expert_activation": "relu"}
# (prompt, new tokens): under a chunk and of nine, under the window and of
# eight windows, outputs that cross a page and the window's edge again
REQUESTS = [(70, 40), (9, 8), (150, 50), (23, 30), (50, 60), (33, 17)]


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


def _engine(config, params, **changes) -> ServingEngine:
    kwargs = dict(num_slots=3, block_size=PAGE, num_blocks=1 + 3 * 40,
                  max_request_len=320, prefill_chunk=16, decode_span=4)
    tenants = changes.pop("tenants", None)
    kwargs.update(changes)
    return ServingEngine(params, config, EngineConfig(**kwargs),
                         tenants=tenants)


def _prompt(seed: int, length: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 500, length).astype(
        np.int32)


def _gaps(params, tc, prompt, served) -> np.ndarray:
    return reference.served_gaps(params, tc, prompt, served)


def _submit_all(engine):
    return [(prompt, engine.submit(Request(f"r{i}", prompt, new)))
            for i, (prompt, new) in enumerate(
                (_prompt(20 + i, p), n) for i, (p, n) in enumerate(REQUESTS))]


def _held_as_they_should_be(engine):
    """Every live lane's window table, after a consume: the entries wholly
    behind its next dispatch's window point at the scratch block, the
    entries from there on hold its pages (as many as it was admitted with,
    fewer near its end), and nothing further is drawn."""
    width = engine._table_width
    for slot in engine._slots:
        if slot.state == "free":
            assert not slot.window_blocks and not slot.table.any()
            continue
        rows = slot.plan[0][0] if slot.plan else slot.length
        first = max(rows - WINDOW + 1, 0) // PAGE
        table = slot.table[width:]
        assert not table[:first].any()
        to = min(len(slot.blocks), first + engine._window_pages)
        assert table[first:to].all() and not table[to:].any()
        assert sorted(table[first:to]) == sorted(slot.window_blocks)
        assert len(slot.window_blocks) <= engine._window_pages
        # the full kind holds every page of the request, as ever
        assert list(slot.table[:len(slot.blocks)]) == slot.blocks


def test_the_engine_serves_what_the_reference_puts_first(model):
    """Six requests through ``submit`` / ``run`` on three slots, in mixed
    dispatches: in float32 every served token is the reference's best; the
    window kind's pages go back while the requests run (more are released
    than were ever reserved), a lane never holds more than it was admitted
    with, and every page of both kinds is back at the end."""
    tc, config, params = model
    engine = _engine(config, params)
    assert engine.pool.by_kind and engine.prefix_index is None
    assert engine.pool.kind_num_blocks == (
        engine.allocator.num_blocks, engine.window_allocator.num_blocks)
    assert [k.shape[0] for k in engine.pool.k] == [1, 3]
    # what a lane of the window kind is funded for: the window, a chunk,
    # a page for the ends
    assert engine._window_pages == -(-(WINDOW + 16 - 1) // PAGE) + 1 == 6
    engine.warmup()
    warm = engine.compile_counts()
    results = _submit_all(engine)
    since = profiling.spans()[-1][1] if profiling.spans() else 0.0
    while engine.step():
        if engine._inflight is None:  # consumed: the tables are settled
            _held_as_they_should_be(engine)
    assert engine.compile_counts() == warm
    for (prompt, result), (_, new) in zip(results, REQUESTS):
        assert len(result.tokens) == new
        assert _gaps(params, tc, prompt, result.tokens).max() == 0.0
    assert engine.allocator.blocks_in_use == 0
    assert engine.window_allocator.blocks_in_use == 0
    counted = engine.kv_kind_blocks
    assert counted["full", "reserved"] == counted["full", "returned"] \
        == sum(-(-(p + n) // PAGE) for p, n in REQUESTS)
    assert counted["window", "reserved"] == sum(
        min(-(-(p + n) // PAGE), 6) for p, n in REQUESTS)
    assert counted["window", "reserved"] + counted["window", "drawn"] \
        == counted["window", "released"] + counted["window", "returned"]
    assert counted["window", "released"] > counted["window", "reserved"]
    spans = profiling.spans(since=since, name="kubeshare.engine.kv_kinds")
    assert spans and all(
        set(r[4]) == {"released", "drawn", "live_full", "live_window",
                      "context_rows"} for r in spans)
    assert sum(r[4]["released"] for r in spans) \
        == counted["window", "released"]
    assert sum(r[4]["drawn"] for r in spans) == counted["window", "drawn"]
    assert max(r[4]["live_window"] for r in spans) <= 3 * 6
    launches = profiling.spans(since=since, name="kubeshare.engine.launch")
    assert {r[4]["attend"] for r in launches} == {"whole"}
    assert all(r[4]["window_rows"] <= r[4]["rows"] for r in launches)
    assert any(0 < r[4]["window_rows"] < r[4]["rows"] for r in launches)
    assert all(r[4]["window_rows"] <= WINDOW * r[4]["lanes"]
               for r in launches)
    families = {f.name: f for f in engine.collect_metrics()}
    family = families["kubeshare_serving_kv_kind_blocks_total"]
    assert {(s.labels["kind"], s.labels["event"]): s.value
            for s in family.samples} == counted


def test_a_released_page_is_never_read(model):
    """The same requests with every released page POISONED the moment it
    goes back (and before another lane can draw it): the same tokens."""
    tc, config, params = model
    plain = _engine(config, params)
    expected = _submit_all(plain)
    plain.run()
    engine = _engine(config, params)
    reclaim = engine.window_allocator.reclaim
    poisoned = []

    def reclaim_and_poison(blocks):
        reclaim(blocks)
        if blocks:
            pages = jnp.asarray(list(blocks))
            poisoned.extend(blocks)
            engine.pool = replace(
                engine.pool,
                k=(engine.pool.k[0], engine.pool.k[1].at[:, pages].set(7.0)),
                v=(engine.pool.v[0], engine.pool.v[1].at[:, pages].set(7.0)))

    engine.window_allocator.reclaim = reclaim_and_poison
    results = _submit_all(engine)
    engine.run()
    assert len(poisoned) > 60
    for (_, result), (_, want) in zip(results, expected):
        assert result.tokens == want.tokens


def test_a_double_release_is_loud(model):
    _, config, params = model
    engine = _engine(config, params)
    engine.submit(Request("r", _prompt(1, 40), 4))
    engine._admit()
    slot = next(s for s in engine._slots if s.state != "free")
    held = list(slot.window_blocks)
    assert len(held) == min(-(-44 // PAGE), engine._window_pages)
    engine.window_allocator.reclaim(held[:2])
    with pytest.raises(ValueError, match="double free"):
        engine.window_allocator.reclaim(held[:2])


def test_admission_fails_cleanly_when_a_kind_cannot_fund_a_request(model):
    """A small pool, 65 pages of the full kind and 9 of the window kind.
    Beside one long request the FULL kind cannot fund a second long one
    (the window kind is never asked); later the WINDOW kind cannot fund a
    second lane of six pages though the full kind can (its pages are given
    back at once).  Either way the request waits in the queue, nothing of
    it is held, and it is served once pages are back."""
    _, config, params = model
    engine = _engine(config, params, num_blocks=24)
    full, near = engine.allocator, engine.window_allocator
    assert (full.num_blocks, near.num_blocks) == (66, 10)
    first = engine.submit(Request("long", _prompt(2, 290), 30))  # 40 pages
    engine._admit()
    assert (full.blocks_in_use, near.blocks_in_use) == (40, 6)
    second = engine.submit(Request("long2", _prompt(3, 200), 40))  # 30 > 25
    engine.step()
    assert (full.blocks_in_use, near.blocks_in_use) == (40, 6)
    assert len(engine._queue) == 1
    engine.run()
    assert len(first.tokens) == 30 and len(second.tokens) == 40
    assert (full.blocks_in_use, near.blocks_in_use) == (0, 0)
    # two requests of 10 pages: the second's six window pages are not there
    results = [engine.submit(Request(f"w{i}", _prompt(4 + i, 60), 20))
               for i in range(2)]
    engine._admit()
    assert (full.blocks_in_use, near.blocks_in_use) == (10, 6)
    assert len(engine._queue) == 1
    engine.run()
    assert all(len(r.tokens) == 20 for r in results)
    assert (full.blocks_in_use, near.blocks_in_use) == (0, 0)
    counted = engine.kv_kind_blocks
    assert counted["full", "reserved"] == counted["full", "returned"] \
        == 40 + 30 + 10 + 10


def test_a_request_the_window_kind_can_never_hold_is_refused(model):
    _, config, params = model
    engine = _engine(config, params, num_blocks=8)
    assert engine.window_allocator.num_blocks - 1 < engine._window_pages
    with pytest.raises(BlockExhausted, match="of the window kind"):
        engine.submit(Request("r", _prompt(1, 100), 20))


def test_a_preempted_request_returns_both_kinds_and_serves_its_tokens(model):
    """A Guarantee admission with no free slot preempts the lane: both
    kinds' pages are dropped whole, and the resumed request prefills prompt
    + generated from row 0 and serves the unpreempted stream."""
    tc, config, params = model
    tenants = TenantRegistry([TenantSpec("gold"), TenantSpec(
        "batch", qos_class=QOS_OPPORTUNISTIC)])
    prompt, gold = _prompt(41, 70), _prompt(42, 40)
    alone = _engine(config, params, num_slots=1)
    expected = alone.submit(Request("alone", prompt, 60))
    alone.run()
    engine = _engine(config, params, num_slots=1, tenants=tenants)
    victim = engine.submit(Request("victim", prompt, 60, tenant="batch"))
    while engine.tokens_generated < 30:
        assert engine.step()
    assert not victim.done
    served = engine.submit(Request("gold", gold, 9, tenant="gold"))
    engine.step()
    assert engine.preemptions == {"batch": 1}
    # the victim's pages of both kinds went back before gold's were drawn
    slot = engine._slots[0]
    assert slot.rid == "gold"
    assert engine.allocator.blocks_in_use == len(slot.blocks) == 7
    assert engine.window_allocator.blocks_in_use \
        == len(slot.window_blocks) == 6
    engine.run()
    assert victim.tokens == expected.tokens and len(victim.tokens) == 60
    assert _gaps(params, tc, prompt, victim.tokens).max() == 0.0
    assert _gaps(params, tc, gold, served.tokens).max() == 0.0
    assert engine.allocator.blocks_in_use == 0
    assert engine.window_allocator.blocks_in_use == 0
    counted = engine.kv_kind_blocks
    assert counted["full", "reserved"] == counted["full", "returned"]


@pytest.mark.parametrize("changes,said", [
    (dict(speculative=True), "speculative=True"),
    (dict(steps_per_launch=2), "steps_per_launch > 1"),
    (dict(mesh_spec=MeshSpec(tp=2)), "mesh_spec"),
    (dict(host_tier_bytes=1 << 20), "host_tier_bytes"),
    (dict(pool_role="prefill", mixed=False), "pool_role='prefill'"),
    (dict(pool_role="decode", mixed=False), "pool_role='decode'"),
    (dict(autotune=True), "autotune=True"),
])
def test_what_cannot_hold_a_cache_by_kind_is_refused(model, changes, said):
    _, config, params = model
    with pytest.raises(ValueError, match=said) as refused:
        _engine(config, params, **changes)
    assert "caches BY LAYER KIND" in str(refused.value)


def test_a_shared_host_tier_and_the_prefix_index_are_refused(model):
    from kubeshare_tpu.serving.kv_tier import HostTier, LRUTierPolicy

    _, config, params = model
    with pytest.raises(ValueError, match="a shared host tier"):
        ServingEngine(params, config, EngineConfig(
            num_slots=2, block_size=8, num_blocks=40, max_request_len=128,
            prefill_chunk=16), shared_host_tier=HostTier(
                1 << 20, LRUTierPolicy()))
    # the prefix index and its copy-on-write matches: never built, whatever
    # prefix_cache says, and no copy program is warmed
    engine = _engine(config, params, prefix_cache=True)
    assert engine.prefix_index is None
    assert engine.allocator.evictor is None


def test_the_kinds_add_no_stage(model):
    """Both kinds' attention stay under ``attention``, the early router
    under ``experts``; the rotation's absence adds no scope."""
    _, config, params = model
    engine = _engine(config, params)
    engine.warmup()
    table = stages.stage_table(stages.program_name("mixed", 16))
    assert {"attention", "kv_write", "experts", "head"} <= set(table.values())
    assert set(table.values()) <= {"attention", "kv_write", "experts",
                                   "head", "unscoped"}
