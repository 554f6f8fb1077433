"""Serving subsystem tests: the fair queue and QoS preemption.

The contract is the one ``tests/test_serving.py`` states: the paged pool +
continuous-batching engine emit EXACTLY the token stream the dense-cache
reference paths emit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models.transformer import transformer_init

from serving_helpers import _small_config

pytestmark = pytest.mark.serving


class TestQoSFairQueue:
    """Satellite/tentpole unit layer: the decayed virtual-time fair
    queue must mirror tokend's share model — Guarantee strictly first,
    lowest decayed service per unit weight within a class, FIFO within
    a tenant, exponential recovery while idle."""

    def _registry(self):
        from kubeshare_tpu.serving import (QOS_OPPORTUNISTIC,
                                           TenantRegistry, TenantSpec)

        return TenantRegistry([
            TenantSpec("gold", weight=1.0),
            TenantSpec("silver", weight=2.0),
            TenantSpec("batch", qos_class=QOS_OPPORTUNISTIC),
        ])

    def test_class_then_weighted_service_order(self):
        from kubeshare_tpu.serving import FairQueue

        clock = [0.0]
        q = FairQueue(self._registry(), window_s=10.0,
                      clock=lambda: clock[0])
        for t in ("gold", "silver", "batch"):
            q.push(t, f"{t}-req")
        # untouched counters: guarantee tenants first, FIFO tie-break
        assert q.order() == ["gold", "silver", "batch"]
        # equal raw service, but silver's weight 2 halves its normalized
        # share -> silver overtakes gold; batch stays last regardless
        q.charge("gold", 100)
        q.charge("silver", 100)
        q.charge("batch", 1)
        assert q.order() == ["silver", "gold", "batch"]
        # an opportunistic tenant with ZERO service still never ranks
        # above a guarantee tenant (the scheduler's priority-first Less)
        assert q.normalized_service("batch") < q.normalized_service("gold")

    def test_decay_recovers_share(self):
        import math

        from kubeshare_tpu.serving import FairQueue

        clock = [0.0]
        q = FairQueue(self._registry(), window_s=10.0,
                      clock=lambda: clock[0])
        q.charge("gold", 80)
        assert q.normalized_service("gold") == pytest.approx(80)
        clock[0] = 10.0  # one window later: service decays to 1/e
        assert q.normalized_service("gold") == pytest.approx(
            80 * math.exp(-1))
        clock[0] = 100.0  # ten windows: effectively forgiven
        assert q.normalized_service("gold") < 0.01

    def test_fifo_within_tenant_and_requeue_front(self):
        from kubeshare_tpu.serving import FairQueue

        q = FairQueue(self._registry())
        q.push("gold", "a")
        q.push("gold", "b")
        assert q.peek("gold") == "a"
        q.requeue_front("gold", "resumed")
        assert q.pop("gold") == "resumed"
        assert q.pop("gold") == "a"
        assert q.pop("gold") == "b"
        assert len(q) == 0 and not q

    def test_unknown_tenant_is_loud(self):
        from kubeshare_tpu.serving import FairQueue

        q = FairQueue(self._registry())
        with pytest.raises(KeyError, match="unknown tenant"):
            q.push("nope", "x")


class TestQoSPreemption:
    """The tentpole's contract: a Guarantee admission the pool cannot
    fund preempts an Opportunistic decode slot, the victim's blocks
    retire into the prefix index, and the victim RESUMES from its first
    uncached token emitting EXACTLY its unpreempted stream — greedy and
    sampled — with zero new compiled shapes."""

    def _registry(self, quota=None):
        from kubeshare_tpu.serving import (QOS_OPPORTUNISTIC,
                                           TenantRegistry, TenantSpec)

        return TenantRegistry([
            TenantSpec("gold"),
            TenantSpec("batch", qos_class=QOS_OPPORTUNISTIC,
                       kv_block_quota=quota),
        ])

    def _engine(self, params, config, registry, **overrides):
        from kubeshare_tpu.serving import EngineConfig, ServingEngine

        kwargs = dict(num_slots=2, block_size=4, num_blocks=13,
                      max_request_len=32, prefill_chunk=8)
        kwargs.update(overrides)
        return ServingEngine(params, config, EngineConfig(**kwargs),
                             tenants=registry)

    def _drive_to_decode(self, engine, rid, min_tokens=2):
        """Step until request ``rid`` is decoding with >= min_tokens
        emitted (so a preemption lands mid-stream, not at a boundary)."""
        while True:
            r = engine.result(rid)
            if (r.first_token_at is not None and not r.done
                    and len([s for s in engine._slots if s.rid == rid
                             and s.state == "decode"])
                    and len([s for s in engine._slots
                             if s.rid == rid][0].generated) >= min_tokens):
                return
            assert engine.step(), f"engine idle before {rid} decoded"

    def test_preempted_then_resumed_greedy_bit_exact(self):
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        registry = self._registry()
        engine = self._engine(params, config, registry)
        engine.warmup()
        baseline = engine.compile_counts()
        rng = np.random.default_rng(21)
        # the victim's decode must be LONG: with the pipelined step an
        # in-flight span is consumed before anyone is sacrificed, so a
        # victim that would finish in that span retires instead of
        # being preempted (the cheaper outcome, deliberately)
        p_batch = rng.integers(0, 64, 17)  # 17 + 14 = 31 rows -> 8 blocks
        p_gold = rng.integers(0, 64, 18)   # 18 + 6 = 24 rows -> 6 blocks
        engine.submit(Request("victim", p_batch, 14, tenant="batch"))
        self._drive_to_decode(engine, "victim")
        # 12-block pool: victim holds 8, gold needs 6 > 4 free -> the
        # Guarantee admission must preempt the Opportunistic decode
        engine.submit(Request("gold", p_gold, 6, tenant="gold"))
        out = engine.run()
        assert engine.preemptions.get("batch", 0) >= 1
        for rid, prompt, new in (("victim", p_batch, 14),
                                 ("gold", p_gold, 6)):
            ref = np.asarray(greedy_decode(
                params, config, jnp.asarray(prompt, jnp.int32)[None],
                new))[0]
            assert out[rid].tokens == list(ref), rid
        # the victim's resume actually hit the cache it was retired into
        assert engine.prefix_hit_requests >= 1
        # blocks all home, zero new compiled shapes (the acceptance bar)
        assert engine.allocator.blocks_in_use == 0
        assert engine.compile_counts() == baseline

    def test_preempted_then_resumed_sampled_bit_exact(self):
        """The key schedule must survive preemption: emission k of the
        original consumes step_keys[k-1], which becomes the resumed
        request's first key — same stream as the dense sampled oracle."""
        from kubeshare_tpu.models.decoding import sample_decode
        from kubeshare_tpu.serving import Request

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        registry = self._registry()
        engine = self._engine(params, config, registry, top_k=10,
                              top_p=0.95)
        rng = np.random.default_rng(22)
        p_batch = rng.integers(0, 64, 17)  # 14 new: survives the
        p_gold = rng.integers(0, 64, 18)   # in-flight span (see greedy)
        key = jax.random.PRNGKey(13)
        engine.submit(Request("victim", p_batch, 14, temperature=0.8,
                              rng=key, tenant="batch"))
        self._drive_to_decode(engine, "victim")
        engine.submit(Request("gold", p_gold, 6, tenant="gold"))
        out = engine.run()
        assert engine.preemptions.get("batch", 0) >= 1
        ref = np.asarray(sample_decode(
            params, config, jnp.asarray(p_batch, jnp.int32)[None], key,
            14, temperature=0.8, top_k=10, top_p=0.95))[0]
        assert out["victim"].tokens == list(ref)

    def test_quota_exhaustion_denies_admission(self):
        """Satellite: a tenant at its KV-block quota queues (other
        tenants keep flowing — no head-of-line across tenants), admits
        once its own cached blocks drain, and a request that can NEVER
        fit the quota fails loudly at submit."""
        from kubeshare_tpu.serving import QuotaExceeded, Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        registry = self._registry(quota=6)
        engine = self._engine(params, config, registry, num_slots=3,
                              num_blocks=25)
        rng = np.random.default_rng(23)
        with pytest.raises(QuotaExceeded, match="NEVER"):
            # 25+3 rows -> 7 blocks > the 6-block quota
            engine.submit(Request("huge", rng.integers(0, 64, 25), 3,
                                  tenant="batch"))
        engine.submit(Request("b0", rng.integers(0, 64, 17), 3,
                              tenant="batch"))  # 5 blocks
        engine.submit(Request("b1", rng.integers(0, 64, 17), 3,
                              tenant="batch"))  # 5 more: over quota
        engine.submit(Request("g0", rng.integers(0, 64, 17), 3,
                              tenant="gold"))
        engine.step()
        # b0 admitted; b1 quota-blocked; gold NOT blocked behind it
        assert engine.result("b0").admitted_at is not None
        assert engine.result("b1").admitted_at is None
        assert engine.result("g0").admitted_at is not None
        assert engine.allocator.tenant_usage("batch") == 5
        out = engine.run()  # b0 retires -> its cached blocks drain ->
        assert len(out["b1"].tokens) == 3  # b1 fits its quota again
        assert engine.allocator.tenant_usage("batch") <= 6

    def test_quota_blocked_guarantee_does_not_preempt(self):
        """Review regression: a Guarantee head blocked on its OWN quota
        must not preempt — a victim's slot cannot cure a quota block,
        and preempting one Opportunistic decode per tick is a thrash
        loop.  The blocked head waits; the victim keeps decoding."""
        from kubeshare_tpu.serving import (QOS_OPPORTUNISTIC, Request,
                                           TenantRegistry, TenantSpec)

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        registry = TenantRegistry([
            TenantSpec("gold", kv_block_quota=6),
            TenantSpec("batch", qos_class=QOS_OPPORTUNISTIC),
        ])
        engine = self._engine(params, config, registry, num_slots=2,
                              num_blocks=25)
        rng = np.random.default_rng(26)
        engine.submit(Request("g0", rng.integers(0, 64, 17), 6,
                              tenant="gold"))  # 6 blocks: quota full
        engine.submit(Request("victim", rng.integers(0, 64, 9), 20,
                              tenant="batch"))
        engine.submit(Request("g1", rng.integers(0, 64, 17), 3,
                              tenant="gold"))  # 5 blocks: quota-blocked
        for _ in range(6):
            engine.step()
        # the quota-blocked gold head never preempted the batch decode
        assert engine.preemptions.get("batch", 0) == 0
        assert engine.result("g1").admitted_at is None
        out = engine.run()  # g0 retires -> gold's cache drains -> g1 fits
        assert engine.preemptions.get("batch", 0) == 0
        assert len(out["g1"].tokens) == 3
        assert len(out["victim"].tokens) == 20

    def test_quota_exact_request_readmits_through_own_cache(self):
        """Review regression (livelock): a request sized EXACTLY to its
        tenant's quota, re-submitted after retiring (so admission takes
        a mid-block prefix hit on its own cached chain), must not wedge
        — the hit path pins the retained chain + CoW source past the
        quota, so admission falls back to a COLD reserve that may evict
        the chain.  Streams stay correct either way."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import (QOS_OPPORTUNISTIC, Request,
                                           TenantRegistry, TenantSpec)

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        registry = TenantRegistry([
            TenantSpec("gold"),
            # 14 + 2 = 16 rows = 4 blocks: exactly the quota
            TenantSpec("batch", qos_class=QOS_OPPORTUNISTIC,
                       kv_block_quota=4),
        ])
        engine = self._engine(params, config, registry)
        rng = np.random.default_rng(27)
        prompt = rng.integers(0, 64, 14)  # match will end mid-block (13)
        engine.submit(Request("b0", prompt, 2, tenant="batch"))
        out0 = engine.run()
        engine.submit(Request("b1", prompt.copy(), 2, tenant="batch"))
        out1 = engine.run()  # must terminate (cold fallback), not spin
        ref = np.asarray(greedy_decode(
            params, config, jnp.asarray(prompt, jnp.int32)[None], 2))[0]
        assert out0["b0"].tokens == list(ref)
        assert out1["b1"].tokens == list(ref)
        assert engine.allocator.tenant_usage("batch") <= 4

    def test_doomed_quota_reserve_keeps_cache(self):
        """Review regression: a reservation the quota can NEVER fit
        (blocked by IN-USE blocks, not cache) must raise without
        draining the tenant's idle-cached blocks — the no-wipe
        discipline the pool-level doomed-check already has."""
        from kubeshare_tpu.serving import BlockAllocator, QuotaExceeded

        alloc = BlockAllocator(num_blocks=12, block_size=4)  # 11 usable
        held = alloc.reserve(7, "live", tenant="t", quota=10)  # in use
        cached = alloc.reserve(3, "old", tenant="t", quota=10)
        alloc.mark_cached(cached)
        alloc.reclaim(cached)  # 3 idle-cached, still charged
        assert alloc.cached_idle_blocks == 3
        with pytest.raises(QuotaExceeded, match="full own-cache drain"):
            alloc.reserve(5, "doomed", tenant="t", quota=10)
        # the doomed attempt did not evict a single cached block
        assert alloc.cached_idle_blocks == 3
        assert alloc.evicted_blocks == 0
        assert alloc.tenant_usage("t") == 10
        alloc.reclaim(held)

    def test_guarantee_reclaims_opportunistic_cached_blocks(self):
        """Satellite regression: idle-cached blocks charged to an
        Opportunistic tenant are the FIRST evicted when a Guarantee
        reservation needs the HBM — and the charge moves off the
        Opportunistic tenant's quota ledger."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        registry = self._registry()
        engine = self._engine(params, config, registry, num_slots=1)
        rng = np.random.default_rng(24)
        p0 = rng.integers(0, 64, 21)  # 21+3 -> 6 blocks
        engine.submit(Request("b0", p0, 3, tenant="batch"))
        engine.run()
        assert engine.allocator.cached_idle_blocks == 6
        assert engine.allocator.tenant_usage("batch") == 6
        # gold needs 8 blocks; only 6 free -> must evict batch's cache
        p1 = rng.integers(0, 64, 29)  # 29+3 -> 8 blocks
        engine.submit(Request("g0", p1, 3, tenant="gold"))
        out = engine.run()
        assert engine.allocator.evicted_blocks > 0
        assert engine.allocator.tenant_usage("batch") < 6
        ref = np.asarray(greedy_decode(
            params, config, jnp.asarray(p1, jnp.int32)[None], 3))[0]
        assert out["g0"].tokens == list(ref)

    def test_allocator_evicts_preferred_tenants_first(self):
        """Allocator-level lock for the class asymmetry: with
        evict_tenants_first, the drain skips colder blocks charged to
        other tenants and takes the preferred victim's instead."""
        from kubeshare_tpu.serving import BlockAllocator

        alloc = BlockAllocator(num_blocks=6, block_size=4)  # 5 usable
        a = alloc.reserve(2, "a", tenant="gold")
        b = alloc.reserve(2, "b", tenant="batch")
        alloc.mark_cached(a + b)
        alloc.reclaim(a)  # gold's blocks idle FIRST -> colder in LRU
        alloc.reclaim(b)
        # plain LRU would evict gold's; the preference must pick batch's
        alloc.reserve(2, "c", tenant="gold",
                      evict_tenants_first={"batch"})
        assert alloc.tenant_usage("gold") >= 2  # gold's cache survived
        assert alloc.tenant_usage("batch") < 2
        assert alloc.evicted_blocks >= 1

    def test_quota_counts_idle_cached_blocks_and_own_drain(self):
        """Allocator-level quota semantics: idle-cached blocks stay on
        the tenant's ledger; a reservation over quota drains the
        tenant's OWN cache before raising."""
        from kubeshare_tpu.serving import BlockAllocator, QuotaExceeded

        alloc = BlockAllocator(num_blocks=9, block_size=4)  # 8 usable
        got = alloc.reserve(4, "a", tenant="t", quota=6)
        alloc.mark_cached(got)
        alloc.reclaim(got)  # all idle-cached, still charged
        assert alloc.tenant_usage("t") == 4
        # 4 cached + 4 new > 6 -> drains its own cache, then fits
        alloc.reserve(4, "b", tenant="t", quota=6)
        assert alloc.tenant_usage("t") <= 6
        with pytest.raises(QuotaExceeded):
            alloc.reserve(4, "c", tenant="t", quota=6)

    def test_qos_metrics_flow_through_collect_metrics(self):
        """Satellite: the per-tenant families ride the same promtext
        surface as everything else — queue depth, quota occupancy,
        tokens, preemptions, TTFT by class."""
        from kubeshare_tpu.serving import Request
        from kubeshare_tpu.utils.promtext import encode_families, parse_text

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        registry = self._registry()
        engine = self._engine(params, config, registry)
        rng = np.random.default_rng(25)
        engine.submit(Request("victim", rng.integers(0, 64, 17), 14,
                              tenant="batch"))
        self._drive_to_decode(engine, "victim")
        engine.submit(Request("gold", rng.integers(0, 64, 18), 6,
                              tenant="gold"))
        engine.run()
        samples = {(s.name, tuple(sorted(s.labels.items()))): s.value
                   for s in parse_text(
                       encode_families(engine.collect_metrics()))}
        assert samples[("kubeshare_serving_preemptions_total",
                        (("tenant", "batch"),))] >= 1
        assert samples[("kubeshare_serving_preemptions_total",
                        (("tenant", "gold"),))] == 0
        assert samples[("kubeshare_serving_tenant_tokens_total",
                        (("tenant", "gold"),))] == 6
        assert samples[("kubeshare_serving_tenant_tokens_total",
                        (("tenant", "batch"),))] == 14
        assert samples[("kubeshare_serving_tenant_queue_depth",
                        (("tenant", "batch"),))] == 0
        assert samples[("kubeshare_serving_tenant_kv_blocks",
                        (("tenant", "gold"),))] >= 0
        # TTFT by class: one guarantee and one opportunistic request
        assert samples[("kubeshare_serving_ttft_by_class_seconds_count",
                        (("qos", "guarantee"),))] == 1
        assert samples[("kubeshare_serving_ttft_by_class_seconds_count",
                        (("qos", "opportunistic"),))] == 1
        # TBT: every token after a request's first gets exactly ONE
        # inter-token observation — the preempted victim's resume gap
        # included (review regression: the stall from its last
        # pre-preemption token to the continuation's first is a real
        # inter-token gap and must not vanish from the histogram)
        assert samples[("kubeshare_serving_tbt_seconds_count",
                        (("qos", "guarantee"),))] == 6 - 1
        assert samples[("kubeshare_serving_tbt_seconds_count",
                        (("qos", "opportunistic"),))] == 14 - 1

    def test_unknown_tenant_rejected_at_submit(self):
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = self._engine(params, config, self._registry())
        with pytest.raises(ValueError, match="unknown tenant"):
            engine.submit(Request("x", np.zeros(4, np.int32), 2,
                                  tenant="nope"))
