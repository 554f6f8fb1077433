"""Serving subsystem tests: the KV tiers under the prefix index (host RAM,
disk, the fleet's fabric).

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


class TestKVTier:
    """KV cache tiering (serving/kv_tier.py): demoted blocks round-trip
    the wire format bit-identically, tier-on streams are bit-exact with
    tier-off across attention variants and sampling, the tenant quota
    ledger uncharges on demotion / re-charges on promotion, the
    QoS-aware policy protects Guarantee host bytes, and nothing
    recompiles after warmup (promotion is one warmed upload shape)."""

    # the demote-then-promote driver sequence: r0 seeds the cache, two
    # flushers (29 tokens -> 8 blocks each on a 12-block pool) drain it
    # through the tier, "hit" re-matches r0's prefix from host RAM
    def _tier_reqs(self, rng, shared):
        return [
            dict(rid="r0", prompt=shared, max_new_tokens=3),
            dict(rid="f1", prompt=rng.integers(0, 64, 29),
                 max_new_tokens=3),
            dict(rid="f2", prompt=rng.integers(0, 64, 29),
                 max_new_tokens=3),
            dict(rid="hit", prompt=np.concatenate(
                [shared, rng.integers(0, 64, 4)]), max_new_tokens=3),
        ]

    def _run_sequentially(self, engine, reqs):
        from kubeshare_tpu.serving import Request

        out = {}
        for req in reqs:
            engine.submit(Request(**req))
            out.update({rid: r.tokens for rid, r in engine.run().items()
                        if r.done})
            engine.pop_finished()
        return out

    def _tier_engine(self, params, config, registry=None, **over):
        from kubeshare_tpu.serving import EngineConfig, ServingEngine

        kwargs = dict(num_slots=1, block_size=4, num_blocks=13,
                      max_request_len=32, prefill_chunk=8,
                      host_tier_bytes=1 << 20)
        kwargs.update(over)
        return ServingEngine(params, config, EngineConfig(**kwargs),
                             tenants=registry)

    def test_wire_roundtrip_bit_identical(self):
        """The wire-format layer: pack -> unpack -> pack is the
        identity, bit for bit, and foreign bytes are rejected loudly —
        the contract a cross-slice shipper will inherit."""
        from kubeshare_tpu.serving import (KV_WIRE_VERSION, pack_block,
                                           unpack_block,
                                           wire_block_bytes)

        rng = np.random.default_rng(0)
        k = rng.standard_normal((2, 2, 4, 8)).astype(np.float32)
        v = rng.standard_normal((2, 2, 4, 8)).astype(np.float32)
        toks = np.asarray([5, 9, 2], np.int32)  # partial block (3 < 4)
        buf = pack_block(toks, k, v)
        assert len(buf) == wire_block_bytes(3, 2, 2, 4, 8, 4)
        t2, k2, v2 = unpack_block(buf)
        assert np.array_equal(t2, toks) and t2.dtype == np.int32
        assert np.array_equal(k2, k) and k2.dtype == k.dtype
        assert np.array_equal(v2, v)
        assert pack_block(t2, k2, v2) == buf  # the identity, re-packed
        assert KV_WIRE_VERSION == 2
        # bfloat16 — the model's flagship dtype — must round-trip too:
        # numpy's .str tag for it is an opaque void ('<V2'), so the
        # format carries the dtype NAME (review regression: promotion
        # crashed on jnp.asarray of a void-dtype slab)
        kb = k.astype(jnp.bfloat16)
        tb, kb2, vb2 = unpack_block(pack_block(toks, np.asarray(kb),
                                               np.asarray(kb)))
        assert kb2.dtype == np.asarray(kb).dtype
        assert np.array_equal(kb2.view(np.uint16),
                              np.asarray(kb).view(np.uint16))
        assert jnp.asarray(kb2).dtype == jnp.bfloat16  # promotion path
        # magic/version rejection requires an INTACT buffer: the v2 crc
        # is checked before any header field, so tampered headers must
        # be re-sealed to reach the magic/version checks at all
        import struct as _struct
        import zlib as _zlib

        def reseal(b: bytes) -> bytes:
            return b[:-4] + _struct.pack(
                "<I", _zlib.crc32(b[:-4]) & 0xFFFFFFFF)

        with pytest.raises(ValueError, match="magic"):
            unpack_block(reseal(b"XXXX" + buf[4:]))
        with pytest.raises(ValueError, match="version"):
            unpack_block(reseal(buf[:4] + b"\x63\x00" + buf[6:]))
        with pytest.raises(ValueError, match="truncated"):
            unpack_block(buf[:10])
        # v2 integrity: any single flipped byte — header, tokens, slab,
        # or the trailer itself — is a typed WireCorruption, loudly
        # distinct from honest foreign bytes
        from kubeshare_tpu.serving.kv_tier import _HEADER, WireCorruption
        for at in (0, 5, _HEADER.size + 1, len(buf) // 2, len(buf) - 1):
            bad = bytearray(buf)
            bad[at] ^= 0x40
            with pytest.raises(WireCorruption):
                unpack_block(bytes(bad))

    def test_demote_promote_roundtrip_is_byte_identical(self):
        """Device rows -> host payload -> device rows, bit for bit:
        capture a cached chain's K/V slabs, flush it through the tier,
        verify the host payloads equal the captured slabs, re-admit the
        prefix and verify the promoted blocks' device rows equal them
        too."""
        from kubeshare_tpu.serving import Request, unpack_block

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = self._tier_engine(params, config)
        rng = np.random.default_rng(7)
        shared = rng.integers(0, 64, 13)
        engine.submit(Request("r0", shared, 3))
        engine.run()
        matched, blocks = engine.prefix_index.match(shared)
        assert matched == 13 and len(blocks) == 4  # 3 full + partial
        slabs = [(np.asarray(engine.pool.k[:, b]),
                  np.asarray(engine.pool.v[:, b])) for b in blocks[:3]]
        for rid in ("f1", "f2"):  # flush the cache through the tier
            engine.submit(Request(rid, rng.integers(0, 64, 29), 3))
            engine.run()
        assert engine.tier_demoted_blocks > 0
        matched, chain = engine.prefix_index.match_tiered(shared)
        assert matched == 13
        host_nodes = [n for n in chain[:3] if n.location == "host"]
        assert len(host_nodes) == 3  # the whole chain spilled
        for node, (k_slab, v_slab) in zip(chain[:3], slabs):
            _, hk, hv = unpack_block(
                engine.host_tier.peek(node.host_key).payload)
            assert np.array_equal(hk, k_slab)  # wire == device rows
            assert np.array_equal(hv, v_slab)
        engine.submit(Request("hit", shared.copy(), 3))
        engine.run()
        assert engine.tier_promoted_blocks >= 3
        matched, blocks = engine.prefix_index.match(shared)
        assert matched >= 12  # device-resident again
        for b, (k_slab, v_slab) in zip(blocks[:3], slabs):
            assert np.array_equal(np.asarray(engine.pool.k[:, b]), k_slab)
            assert np.array_equal(np.asarray(engine.pool.v[:, b]), v_slab)

    def test_streams_bit_exact_with_tier_across_configs(self):
        """Tier on vs tier off, token for token, through forced
        demote -> promote cycles — GQA, windowed, and MoE attention."""
        cases = {
            "gqa_rope": dict(n_kv_heads=2, positional="rope"),
            "windowed": dict(attention_window=6),
            "moe": dict(moe_every=2, moe_num_experts=4, moe_top_k=2),
        }
        rng = np.random.default_rng(11)
        shared = rng.integers(0, 64, 13)
        reqs = self._tier_reqs(rng, shared)
        for name, extra in cases.items():
            config = _small_config(**extra)
            params = transformer_init(jax.random.PRNGKey(0), config)
            tiered = self._tier_engine(params, config)
            plain = self._tier_engine(params, config,
                                      host_tier_bytes=None)
            got = self._run_sequentially(tiered, reqs)
            want = self._run_sequentially(plain, reqs)
            assert got == want, name
            assert tiered.tier_demoted_blocks > 0, name
            assert tiered.tier_promoted_blocks > 0, name
            assert tiered.tier_hit_requests > 0, name
            assert plain.tier_demoted_blocks == 0

    def test_sampled_streams_bit_exact_with_tier(self):
        """The key schedule survives a host-tier hit: sampled requests
        through demote/promote emit exactly the tier-off streams."""
        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(13)
        shared = rng.integers(0, 64, 13)
        reqs = []
        for i, req in enumerate(self._tier_reqs(rng, shared)):
            req.update(temperature=0.8, rng=jax.random.PRNGKey(40 + i))
            reqs.append(req)
        tiered = self._tier_engine(params, config, top_k=10)
        plain = self._tier_engine(params, config, top_k=10,
                                  host_tier_bytes=None)
        got = self._run_sequentially(tiered, reqs)
        want = self._run_sequentially(plain, reqs)
        assert got == want
        assert tiered.tier_promoted_blocks > 0

    def test_cow_divergence_on_promoted_block(self):
        """A prompt diverging mid-block INSIDE a promoted block takes
        the standard CoW path (the promoted block is shared state) and
        still emits its solo reference stream."""
        from kubeshare_tpu.models.decoding import greedy_decode

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = self._tier_engine(params, config)
        rng = np.random.default_rng(17)
        shared = rng.integers(0, 64, 13)
        diverge = np.concatenate([shared, rng.integers(0, 64, 4)])
        diverge[9] = (diverge[9] + 1) % 64  # inside the 3rd block
        reqs = self._tier_reqs(rng, shared) + [
            dict(rid="cow", prompt=diverge, max_new_tokens=4)]
        got = self._run_sequentially(engine, reqs)
        assert engine.tier_promoted_blocks >= 3   # "hit" promoted
        assert engine.cow_copies >= 1             # "cow" diverged on it
        ref = np.asarray(greedy_decode(
            params, config, jnp.asarray(diverge, jnp.int32)[None], 4))[0]
        assert got["cow"] == list(ref)

    def test_qos_policy_protects_guarantee_host_bytes(self):
        """The tenant-aware policy's asymmetry, at the store level:
        Guarantee pressure evicts Opportunistic entries first (even
        when a Guarantee entry is colder), and Opportunistic pressure
        that could only fit by evicting Guarantee bytes is REFUSED —
        the incoming block drops instead."""
        from kubeshare_tpu.serving import (QOS_OPPORTUNISTIC, HostTier,
                                           QoSTierPolicy, TenantRegistry,
                                           TenantSpec)

        registry = TenantRegistry([
            TenantSpec("gold"),
            TenantSpec("batch", qos_class=QOS_OPPORTUNISTIC)])
        tier = HostTier(3 * 100, QoSTierPolicy(registry))
        pay = b"x" * 100
        g_old = tier.put(pay, "gold", None)   # coldest entry
        b_mid = tier.put(pay, "batch", None)
        g_new = tier.put(pay, "gold", None)
        assert len(tier) == 3  # budget exactly full
        # Guarantee incoming: the batch entry goes, NOT the colder gold
        g_more = tier.put(pay, "gold", None)
        assert g_more is not None
        keys = {e.key for _, e in tier.iter_lru()}
        assert b_mid not in keys and g_old in keys and g_new in keys
        assert tier.evicted_blocks == 1
        # Opportunistic incoming vs an all-Guarantee store: refused
        assert tier.put(pay, "batch", None) is None
        assert tier.refused_blocks == 1
        assert len(tier) == 3 and g_more in {
            e.key for _, e in tier.iter_lru()}

    def test_guarantee_demotion_evicts_opportunistic_host_blocks(self):
        """Engine-level class asymmetry: with the qos tier policy and a
        host budget already holding Guarantee entries, an Opportunistic
        tenant's spills are dropped (the Guarantee prefix survives) and
        the Guarantee tenant's later re-admission promotes from host."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import (QOS_OPPORTUNISTIC, Request,
                                           TenantRegistry, TenantSpec,
                                           wire_block_bytes)

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        registry = TenantRegistry([
            TenantSpec("gold"),
            TenantSpec("batch", qos_class=QOS_OPPORTUNISTIC)])
        full_wire = wire_block_bytes(4, config.n_layers, config.kv_heads,
                                     4, config.head_dim, 4)
        engine = self._tier_engine(
            params, config, registry=registry, tier_policy="qos",
            host_tier_bytes=4 * full_wire + 200)
        rng = np.random.default_rng(23)
        shared = rng.integers(0, 64, 13)
        engine.submit(Request("g0", shared, 3, tenant="gold"))
        engine.run()
        # batch flushers: gold's chain demotes (charged to gold), then
        # batch's own spills must NOT evict it — they drop
        for i, rid in enumerate(("b1", "b2")):
            engine.submit(Request(rid, rng.integers(0, 64, 29), 3,
                                  tenant="batch"))
            engine.run()
        assert engine.tier_demoted_blocks > 0
        assert engine.tier_dropped_blocks > 0  # batch spills refused
        tenants_left = {e.tenant for _, e in engine.host_tier.iter_lru()}
        assert tenants_left == {"gold"}  # Guarantee bytes survived
        hit = np.concatenate([shared, rng.integers(0, 64, 4)])
        engine.submit(Request("ghit", hit, 3, tenant="gold"))
        out = engine.run()
        assert engine.tier_promoted_blocks > 0
        ref = np.asarray(greedy_decode(
            params, config, jnp.asarray(hit, jnp.int32)[None], 3))[0]
        assert out["ghit"].tokens == list(ref)

    def test_demotion_uncharges_quota_promotion_recharges(self):
        """The quota-honesty satellite, regression-locked: a tenant
        whose idle cache was DEMOTED stops being charged for it (a
        quota-sized request then admits), and promotion re-charges the
        blocks through the normal reservation."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request, TenantRegistry, TenantSpec

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        registry = TenantRegistry([
            TenantSpec("t", kv_block_quota=6), TenantSpec("u")])
        engine = self._tier_engine(params, config, registry=registry)
        rng = np.random.default_rng(29)
        shared = rng.integers(0, 64, 13)
        engine.submit(Request("a", shared, 3, tenant="t"))
        engine.run()
        assert engine.allocator.tenant_usage("t") == 4  # idle, charged
        for rid in ("u1", "u2"):  # u's traffic demotes t's cache
            engine.submit(Request(rid, rng.integers(0, 64, 29), 3,
                                  tenant="u"))
            engine.run()
        assert engine.tier_demoted_blocks > 0
        assert engine.allocator.tenant_usage("t") == 0  # uncharged
        # quota-sized request admits cleanly (17 + 7 = 24 rows = 6
        # blocks = the whole quota — impossible if the demoted cache
        # still occupied the ledger)
        p_big = rng.integers(0, 64, 17)
        engine.submit(Request("b", p_big, 7, tenant="t"))
        out = engine.run()
        ref = np.asarray(greedy_decode(
            params, config, jnp.asarray(p_big, jnp.int32)[None], 7))[0]
        assert out["b"].tokens == list(ref)
        # promotion re-charges: t's host-resident prefix comes back as
        # a normal charged reservation
        engine.submit(Request("a2", np.concatenate(
            [shared, rng.integers(0, 64, 4)]), 3, tenant="t"))
        out = engine.run()
        assert engine.tier_promoted_blocks > 0
        assert engine.allocator.tenant_usage("t") >= 3
        assert engine.allocator.tenant_usage("t") <= 6  # quota held

    def test_eviction_reason_metrics(self):
        """The eviction family's `reason` label: reservation pressure
        and quota drain when tiering is off, tier_demote / tier_drop
        when the tier is consulted — all four series always present."""
        from kubeshare_tpu.serving import Request, TenantRegistry, TenantSpec

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(31)
        # tiering OFF: a quota own-drain, then reservation pressure
        registry = TenantRegistry([
            TenantSpec("t", kv_block_quota=6), TenantSpec("u")])
        plain = self._tier_engine(params, config, registry=registry,
                                  host_tier_bytes=None)
        plain.submit(Request("a", rng.integers(0, 64, 13), 3, tenant="t"))
        plain.run()
        plain.submit(Request("b", rng.integers(0, 64, 17), 7, tenant="t"))
        plain.run()  # 4 cached + 6 needed > 6 -> own-cache quota drain
        assert plain.evictions_by_reason["quota_drain"] > 0
        plain.submit(Request("c", rng.integers(0, 64, 29), 3, tenant="u"))
        plain.run()
        assert plain.evictions_by_reason["reservation_pressure"] > 0
        assert plain.evictions_by_reason["tier_demote"] == 0
        families = {f.name: f for f in plain.collect_metrics()}
        fam = families["kubeshare_serving_prefix_evicted_blocks_total"]
        reasons = {s.labels["reason"] for s in fam.samples}
        assert reasons == {"reservation_pressure", "quota_drain",
                           "tier_demote", "tier_drop"}
        total = sum(s.value for s in fam.samples)
        assert total == plain.allocator.evicted_blocks
        # tiering ON: the same pressure reads tier_demote (and
        # tier_drop once the host budget refuses)
        tiered = self._tier_engine(params, config)
        shared = rng.integers(0, 64, 13)
        for req in self._tier_reqs(rng, shared):
            tiered.submit(Request(**req))
            tiered.run()
        assert tiered.evictions_by_reason["tier_demote"] > 0
        assert tiered.evictions_by_reason["reservation_pressure"] == 0

    def test_host_budget_lru_eviction_and_pinning(self):
        """The store's budget discipline: LRU eviction keeps
        used_bytes under budget, pinned entries are never victims, and
        an all-pinned store refuses the incoming block."""
        from kubeshare_tpu.serving import HostTier, LRUTierPolicy

        tier = HostTier(2 * 100, LRUTierPolicy())
        pay = b"x" * 100
        k1 = tier.put(pay, None, None)
        k2 = tier.put(pay, None, None)
        k3 = tier.put(pay, None, None)  # evicts k1 (coldest)
        keys = {e.key for _, e in tier.iter_lru()}
        assert keys == {k2, k3} and tier.used_bytes == 200
        assert tier.evicted_blocks == 1
        tier.pin(k2)
        k4 = tier.put(pay, None, None)  # k2 pinned -> k3 goes
        assert {e.key for _, e in tier.iter_lru()} == {k2, k4}
        tier.pin(k4)
        assert tier.put(pay, None, None) is None  # all pinned: refused
        assert tier.refused_blocks == 1
        tier.unpin(k2)
        assert tier.put(pay, None, None) is not None
        # oversized payloads can never fit and are refused up front
        assert tier.put(b"y" * 300, None, None) is None

    def test_subtree_demotion_survives_one_block_host_budget(self):
        """Review regression: demoting a multi-block subtree under a
        host budget too small for all of it must NOT let the tier evict
        the just-demoted ancestor to fund its own descendants — the
        ancestor transiently has device-resident children mid-walk, and
        detaching it then corrupted trie/allocator state (RuntimeError
        under the allocator lock).  Walk-local pinning makes the
        descendants DROP instead, and every device block still comes
        back to the free list."""
        from kubeshare_tpu.serving import Request, wire_block_bytes

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        full_wire = wire_block_bytes(4, config.n_layers, config.kv_heads,
                                     4, config.head_dim, 4)
        engine = self._tier_engine(params, config,
                                   host_tier_bytes=full_wire)
        rng = np.random.default_rng(41)
        shared = rng.integers(0, 64, 13)
        engine.submit(Request("r0", shared, 3))
        engine.run()
        # evict the CHAIN HEAD directly — the victim shape reserve's
        # preferred-tenant scan produces for a mixed-charge chain (its
        # head can be the first idle block charged to the preferred
        # victim tenant, taking the whole subtree parent-first)
        matched, blocks = engine.prefix_index.match(shared)
        assert matched == 13
        with engine.allocator._lock:
            engine.allocator._evict_locked(blocks[0],
                                           "reservation_pressure")
        # head demoted (pinned through the walk), descendants dropped
        # when the one-entry budget could not take them; nothing raised
        assert engine.tier_demoted_blocks == 1
        assert engine.tier_dropped_blocks == 3
        assert len(engine.host_tier) == 1
        survivor = next(e.key for _, e in engine.host_tier.iter_lru())
        assert not engine.host_tier.is_pinned(survivor)  # pin released
        # allocator conservation: every block is free or idle-cached
        assert (engine.allocator.free_blocks
                + engine.allocator.cached_idle_blocks
                == engine.allocator.num_blocks - 1)

    def test_zero_recompiles_with_tier_promotions(self):
        """Acceptance criterion: warmup covers the upload shape, so a
        workload full of demotions and promotions adds ZERO compiled
        shapes beyond the warmed set."""
        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = self._tier_engine(params, config)
        engine.warmup()
        baseline = engine.compile_counts()
        assert baseline["upload"] == 1  # the tier's single extra shape
        rng = np.random.default_rng(37)
        shared = rng.integers(0, 64, 13)
        self._run_sequentially(engine, self._tier_reqs(rng, shared))
        assert engine.tier_demoted_blocks > 0
        assert engine.tier_promoted_blocks > 0
        assert engine.compile_counts() == baseline


class TestDiskTier:
    """The mmap-backed DISK tier below host RAM (serving/kv_tier.py
    DiskTier + the engine's HOST→DISK demotion cascade and
    DISK→HOST→device promotion staging): arena round-trips are byte
    identical, the byte budget refuses and evicts like the host store,
    disk-tier-on streams are bit-exact with tier-off, and the gauges
    land on the metrics plane."""

    def _reqs(self, rng, shared):
        return [
            dict(rid="r0", prompt=shared, max_new_tokens=3),
            dict(rid="f1", prompt=rng.integers(0, 64, 29),
                 max_new_tokens=3),
            dict(rid="f2", prompt=rng.integers(0, 64, 29),
                 max_new_tokens=3),
            dict(rid="hit", prompt=np.concatenate(
                [shared, rng.integers(0, 64, 4)]), max_new_tokens=3),
        ]

    def _run_sequentially(self, engine, reqs):
        from kubeshare_tpu.serving import Request

        out = {}
        for req in reqs:
            engine.submit(Request(**req))
            out.update({rid: r.tokens for rid, r in engine.run().items()
                        if r.done})
            engine.pop_finished()
        return out

    def _disk_engine(self, params, config, **over):
        from kubeshare_tpu.serving import (EngineConfig, ServingEngine,
                                           wire_block_bytes)

        full_wire = wire_block_bytes(4, config.n_layers, config.kv_heads,
                                     4, config.head_dim, 4)
        kwargs = dict(num_slots=1, block_size=4, num_blocks=13,
                      max_request_len=32, prefill_chunk=8,
                      host_tier_bytes=3 * full_wire,
                      disk_tier_bytes=1 << 20)
        kwargs.update(over)
        return ServingEngine(params, config, EngineConfig(**kwargs))

    def test_arena_roundtrip_budget_and_hole_reuse(self):
        """The store itself: put/read/take are byte identical through
        the mmap (including across a growth re-map), the PAYLOAD-byte
        budget evicts LRU (never pins) and refuses oversized blocks,
        and freed extents coalesce for reuse."""
        from kubeshare_tpu.serving import DiskTier

        tier = DiskTier(budget_bytes=300)
        a = tier.put(b"a" * 100, None, None)
        b = tier.put(b"b" * 100, None, None)
        c = tier.put(b"c" * 100, None, None)
        assert tier.read(a) == b"a" * 100
        assert tier.used_bytes == 300
        # budget full: the next put evicts the coldest (b — a was
        # touched by the read above)
        d = tier.put(b"d" * 100, None, None)
        assert tier.probe(b) is None and tier.evicted_blocks == 1
        assert tier.read(d) == b"d" * 100
        # take() promotes: bytes come back identical, space frees
        assert tier.take(c) == b"c" * 100
        assert tier.promoted_blocks == 1 and tier.used_bytes == 200
        # pinned entries are never victims; an all-pinned store refuses
        for key in (a, d):
            tier.pin(key)
        e = tier.put(b"e" * 100, None, None)
        assert e is not None  # c's hole funds it without eviction
        tier.pin(e)
        assert tier.put(b"f" * 100, None, None) is None
        assert tier.refused_blocks == 1
        # over-budget payloads are refused up front
        assert tier.put(b"x" * 301, None, None) is None
        # growth re-map preserves existing payloads bit for bit
        big = DiskTier(budget_bytes=1 << 22)
        k1 = big.put(b"q" * 37, None, None)
        k2 = big.put(b"z" * (1 << 20), None, None)  # forces _grow
        assert big.read(k1) == b"q" * 37
        assert big.read(k2) == b"z" * (1 << 20)
        tier.close()
        big.close()

    def test_named_arena_file_is_a_real_mmap_file(self, tmp_path):
        """disk_tier_path pins the arena to a caller-named file — the
        handle a process on the other side can open; payloads placed
        through it read back byte identical from a fresh mmap of the
        same file."""
        import mmap as _mmap
        import os as _os

        from kubeshare_tpu.serving import DiskTier

        path = str(tmp_path / "kv.arena")
        tier = DiskTier(budget_bytes=1 << 16, path=path)
        payload = bytes(np.random.default_rng(0).integers(
            0, 256, 777, dtype=np.uint8))
        key = tier.put(payload, None, None)
        entry = tier.probe(key)
        fd = _os.open(path, _os.O_RDONLY)
        try:
            mm = _mmap.mmap(fd, 0, prot=_mmap.PROT_READ)
            assert bytes(mm[entry.offset: entry.offset
                            + entry.nbytes]) == payload
            mm.close()
        finally:
            _os.close(fd)
        tier.close()

    def test_streams_bit_exact_with_disk_tier_across_configs(self):
        """Disk tier on vs everything off, token for token, through a
        forced HOST→DISK→HOST→device cascade (the host budget takes 3
        wire blocks, the flushers demote 8+) — GQA and windowed
        attention included."""
        cases = {
            "plain": dict(),
            "gqa_rope": dict(n_kv_heads=2, positional="rope"),
            "windowed": dict(attention_window=6),
        }
        rng = np.random.default_rng(11)
        shared = rng.integers(0, 64, 13)
        reqs = self._reqs(rng, shared)
        for name, extra in cases.items():
            config = _small_config(**extra)
            params = transformer_init(jax.random.PRNGKey(0), config)
            disked = self._disk_engine(params, config)
            plain = self._disk_engine(params, config,
                                      host_tier_bytes=None,
                                      disk_tier_bytes=None)
            got = self._run_sequentially(disked, reqs)
            want = self._run_sequentially(plain, reqs)
            assert got == want, name
            assert disked.disk_tier.stored_blocks > 0, name
            assert disked.disk_tier.promoted_blocks > 0, name
            assert disked.tier_hit_requests_by_origin["local"] >= 1

    def test_sampled_streams_bit_exact_with_disk_tier(self):
        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(13)
        shared = rng.integers(0, 64, 13)
        reqs = []
        for i, req in enumerate(self._reqs(rng, shared)):
            req.update(temperature=0.8, rng=jax.random.PRNGKey(40 + i))
            reqs.append(req)
        disked = self._disk_engine(params, config, top_k=10)
        plain = self._disk_engine(params, config, top_k=10,
                                  host_tier_bytes=None,
                                  disk_tier_bytes=None)
        got = self._run_sequentially(disked, reqs)
        want = self._run_sequentially(plain, reqs)
        assert got == want
        assert disked.disk_tier.promoted_blocks > 0

    def test_zero_recompiles_with_disk_promotions(self):
        """The cascade adds no dispatch shapes: promotion from disk
        rides the SAME warmed upload path a host hit uses."""
        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = self._disk_engine(params, config)
        engine.warmup()
        baseline = engine.compile_counts()
        rng = np.random.default_rng(37)
        shared = rng.integers(0, 64, 13)
        self._run_sequentially(engine, self._reqs(rng, shared))
        assert engine.disk_tier.promoted_blocks > 0
        assert engine.compile_counts() == baseline

    def test_disk_gauges_on_metrics_plane(self):
        from kubeshare_tpu.serving import flatten_metrics, metric_value

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = self._disk_engine(params, config)
        rng = np.random.default_rng(11)
        shared = rng.integers(0, 64, 13)
        self._run_sequentially(engine, self._reqs(rng, shared))
        fams = flatten_metrics(engine.collect_metrics())
        assert metric_value(fams, "kubeshare_serving_disk_tier_blocks_total",
                            event="demoted") > 0
        assert metric_value(fams, "kubeshare_serving_disk_tier_blocks_total",
                            event="promoted") > 0
        assert metric_value(fams, "kubeshare_serving_disk_tier_bytes",
                            kind="budget") == 1 << 20
        assert metric_value(fams, "kubeshare_serving_disk_tier_bytes",
                            kind="used") >= 0
        # the remote-vs-local tier-hit split is on the plane too
        assert metric_value(
            fams, "kubeshare_serving_tier_hit_origin_requests_total",
            origin="local") >= 1
        assert metric_value(
            fams, "kubeshare_serving_tier_hit_origin_requests_total",
            origin="remote") == 0

    def test_config_validation_is_loud(self):
        from kubeshare_tpu.serving import EngineConfig, ServingEngine

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        with pytest.raises(ValueError, match="requires host_tier_bytes"):
            ServingEngine(params, config, EngineConfig(
                num_slots=1, block_size=4, num_blocks=13,
                max_request_len=32, disk_tier_bytes=1 << 20))
        with pytest.raises(ValueError, match="disk_tier_path"):
            ServingEngine(params, config, EngineConfig(
                num_slots=1, block_size=4, num_blocks=13,
                max_request_len=32, host_tier_bytes=1 << 20,
                disk_tier_path="/tmp/x.arena"))


class TestFabric:
    """The cluster KV fabric (serving/fabric.py): envelope honesty
    (crc-first, loud corruption), bit-identical chain round-trips over
    a REAL socketpair, at-least-once endpoint delivery with ack/dedup/
    TTL/bounded backoff, the prefix directory's remote-affinity hook in
    fleet routing, drain inheritance riding the fabric, the disagg
    ticket bus, and the exportable prefix store."""

    def test_message_envelope_roundtrip_and_corruption(self):
        from kubeshare_tpu.serving import (WireCorruption, pack_message,
                                           unpack_message)
        from kubeshare_tpu.serving.fabric import K_CHAIN

        body = b"\x01payload bytes\xff" * 9
        frame = pack_message(K_CHAIN, 42, "alpha", "beta", body)
        kind, mid, src, dest, got = unpack_message(frame)
        assert (kind, mid, src, dest, got) == (
            K_CHAIN, 42, "alpha", "beta", body)
        # any single flipped bit — header, body, crc trailer — is a
        # typed WireCorruption, checked BEFORE any envelope field
        for at in (0, 3, 11, len(frame) // 2, len(frame) - 1):
            bad = bytearray(frame)
            bad[at] ^= 0x10
            with pytest.raises(WireCorruption):
                unpack_message(bytes(bad))
        with pytest.raises(WireCorruption, match="truncated"):
            unpack_message(frame[:8])
        # intact-but-foreign frames are plain ValueErrors (re-sealed so
        # the crc passes and the magic/version checks are reachable)
        import struct as _struct
        import zlib as _zlib

        def reseal(b: bytes) -> bytes:
            return b[:-4] + _struct.pack(
                "<I", _zlib.crc32(b[:-4]) & 0xFFFFFFFF)

        with pytest.raises(ValueError, match="magic"):
            unpack_message(reseal(b"XXXX" + frame[4:]))
        with pytest.raises(ValueError, match="version"):
            unpack_message(reseal(frame[:4] + b"\x63\x00" + frame[6:]))
        with pytest.raises(ValueError, match="over 16 bytes"):
            pack_message(K_CHAIN, 0, "x" * 17, "beta", b"")

    def test_chain_roundtrip_over_socketpair_bit_identical(self):
        """Satellite wire-honesty lock: a packed prefix chain crosses a
        REAL OS socketpair and unpacks to byte-identical payloads and
        device rows — float32 and bfloat16 — and a single flipped bit
        anywhere in the frame is a loud WireCorruption on the far
        side.  Locked against the v2 block format fixtures."""
        import socket as _socket

        from kubeshare_tpu.serving import (KV_WIRE_VERSION,
                                           WireCorruption, pack_block,
                                           pack_message, recv_frame,
                                           send_frame, unpack_block,
                                           unpack_message)
        from kubeshare_tpu.serving.fabric import (K_CHAIN,
                                                  pack_chain_msg,
                                                  unpack_chain_msg)

        assert KV_WIRE_VERSION == 2
        rng = np.random.default_rng(7)
        items = []
        toks = rng.integers(0, 64, 8).astype(np.int32)
        for i, dt in enumerate((np.float32, jnp.bfloat16)):
            k = np.asarray(
                rng.standard_normal((2, 2, 4, 8)).astype(np.float32))
            k = np.asarray(jnp.asarray(k, dt)) if dt is jnp.bfloat16 \
                else k
            # cumulative root-to-node token path, per-BLOCK payload
            payload = pack_block(toks[4 * i: 4 * (i + 1)], k, k)
            items.append((toks[:4 * (i + 1)], payload))
        frame = pack_message(
            K_CHAIN, 0, "sender", "receiver",
            pack_chain_msg("tenant-a", items))

        a, b = _socket.socketpair()
        try:
            send_frame(a, frame)
            got_frame = recv_frame(b)
            assert got_frame == frame  # the transport is byte-honest
            _, _, _, _, body = unpack_message(got_frame)
            tenant, got_items = unpack_chain_msg(body)
            assert tenant == "tenant-a"
            assert len(got_items) == len(items)
            for (toks0, pay0), (toks1, pay1) in zip(items, got_items):
                assert np.array_equal(toks0, toks1)
                assert pay0 == pay1  # byte identical through the wire
                t0, k0, v0 = unpack_block(pay0)
                t1, k1, v1 = unpack_block(pay1)
                assert np.array_equal(t0, t1)
                assert k0.dtype == k1.dtype
                assert np.array_equal(k0.view(np.uint8),
                                      k1.view(np.uint8))
                assert np.array_equal(v0.view(np.uint8),
                                      v1.view(np.uint8))
            # a flipped bit in transit is LOUD on the receiving side
            bad = bytearray(frame)
            bad[len(bad) // 2] ^= 0x01
            send_frame(a, bytes(bad))
            with pytest.raises(WireCorruption):
                unpack_message(recv_frame(b))
        finally:
            a.close()
            b.close()

    def test_chain_survives_disk_arena_byte_identical(self):
        """The same honesty through the mmap file: a wire-v2 payload
        parked in the DISK arena reads back byte identical, and a
        rotted byte on the platter is a WireCorruption at unpack."""
        from kubeshare_tpu.serving import (DiskTier, WireCorruption,
                                           pack_block, unpack_block)

        rng = np.random.default_rng(9)
        k = rng.standard_normal((2, 2, 4, 8)).astype(np.float32)
        payload = pack_block(np.arange(4, dtype=np.int32), k, k)
        tier = DiskTier(budget_bytes=1 << 16)
        key = tier.put(payload, None, None)
        assert tier.read(key) == payload
        t2, k2, v2 = unpack_block(tier.read(key))
        assert np.array_equal(k2, k) and np.array_equal(v2, k)
        # rot the platter directly (no chaos clock): loud at unpack
        entry = tier.probe(key)
        tier._mm[entry.offset + 11] ^= 0x20
        with pytest.raises(WireCorruption):
            unpack_block(tier.read(key))
        tier.close()

    def test_endpoint_ack_dedup_redelivery_and_ttl(self):
        """The at-least-once contract end to end: a dropped frame is
        retransmitted under bounded backoff and delivered exactly once;
        a dropped ACK triggers a redelivery the receiver absorbs as a
        duplicate (re-acking it); a partitioned destination expires
        after ttl_ticks and surfaces through take_expired."""
        from kubeshare_tpu.serving import (FabricEndpoint,
                                           LoopbackTransport)
        from kubeshare_tpu.serving.fabric import K_CHAIN

        class _Flaky(LoopbackTransport):
            def __init__(self):
                super().__init__()
                self.drop_next = 0

            def send(self, dest, frame):
                if self.drop_next > 0:
                    self.drop_next -= 1
                    return
                super().send(dest, frame)

        tr = _Flaky()
        a = FabricEndpoint("a", tr, ttl_ticks=8)
        b = FabricEndpoint("b", tr, ttl_ticks=8)
        # 1) dropped data frame -> backoff redelivery -> one delivery
        tr.drop_next = 1
        mid = a.send("b", K_CHAIN, b"hello")
        assert b.poll() == [] and a.inflight == 1
        a.tick()  # due: retransmit
        got = b.poll()
        assert [(s, k, m, body) for s, k, m, body in got] == [
            ("a", K_CHAIN, mid, b"hello")]
        assert a.poll() == []  # acks are absorbed, not surfaced
        assert a.take_delivered() == [mid] and a.inflight == 0
        assert a.redeliveries == 1
        # 2) dropped ACK -> redelivery -> receiver dedups and re-acks
        mid2 = a.send("b", K_CHAIN, b"again")
        tr.drop_next = 1  # the ack is the next frame b sends
        assert len(b.poll()) == 1
        assert a.poll() == [] and a.inflight == 1  # ack lost
        a.tick()
        assert b.poll() == []  # duplicate absorbed, re-acked
        assert b.messages[("chain", "duplicate")] == 1
        a.poll()
        assert a.take_delivered() == [mid2] and a.inflight == 0
        # 3) partition: every transmit dropped until TTL
        tr.drop_next = 10 ** 6
        mid3 = a.send("b", K_CHAIN, b"doomed")
        for _ in range(8):
            a.tick()
        assert a.inflight == 0
        assert a.take_expired() == [("b", K_CHAIN, mid3, b"doomed")]
        assert a.messages[("chain", "expired")] == 1
        # counters reconcile: delivered + expired == sent
        assert (a.messages[("chain", "delivered")]
                + a.messages[("chain", "expired")]
                == a.messages[("chain", "sent")])

    def test_ticket_body_roundtrip(self):
        from kubeshare_tpu.serving import pack_ticket, unpack_ticket

        keys = np.asarray([[1, 2], [3, 4]], np.uint32)
        body = pack_ticket(
            "rid-1", "tenant-b", np.arange(7, dtype=np.int32), 11, 5,
            0.8, keys, b"\x00wire\xff", [11, 3], np.asarray([3, 1],
                                                            np.int32),
            0.25, last_token_at=123.5)
        d = unpack_ticket(body)
        assert d["rid"] == "rid-1" and d["tenant"] == "tenant-b"
        assert np.array_equal(d["prompt"], np.arange(7))
        assert (d["first_token"], d["max_new"]) == (11, 5)
        assert d["temperature"] == 0.8
        assert np.array_equal(d["step_keys"], keys)
        assert d["payload"] == b"\x00wire\xff"
        assert d["emitted_prefix"] == [11, 3]
        assert list(d["hint"]) == [3, 1]
        assert d["pack_stall_s"] == 0.25
        assert d["last_token_at"] == 123.5
        # greedy: empty key schedule, no hint, no last-token timestamp
        d2 = unpack_ticket(pack_ticket(
            "r", "t", np.asarray([1], np.int32), 0, 1, 0.0,
            np.zeros((0, 0), np.uint32), b"", [], np.asarray([],
                                                             np.int32),
            0.0))
        assert d2["step_keys"].size == 0 and d2["hint"].size == 0
        assert d2["last_token_at"] is None

    def test_remote_affinity_routes_via_directory(self):
        """A trie miss everywhere + a directory hit routes to the
        publishing owner (reason remote_affinity) instead of
        least-loaded — the fabric's re-prefill saver."""
        from kubeshare_tpu.serving import (EngineConfig, ReplicaFleet,
                                           Request)
        from kubeshare_tpu.serving.fabric import (LoopbackTransport,
                                                  prefix_fabric_key)

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        fleet = ReplicaFleet(
            params, config,
            EngineConfig(num_slots=3, block_size=4, num_blocks=21,
                         max_request_len=48, prefill_chunk=8),
            replicas=2, shared_tier_bytes=1 << 20,
            fabric=LoopbackTransport())
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, 64, 14)
        target = fleet.replicas[1].name
        # publish the 12-token block boundary as held by replica 1
        fleet.directory.publish(prefix_fabric_key(prompt[:12]), target,
                                token_len=12)
        fleet.submit(Request("q", prompt, 3))
        fleet.run()
        assert fleet.owner_of("q") == target
        assert fleet.routing_decisions["remote_affinity"] == 1
        # a withdrawn owner falls back to least-loaded (staleness-safe)
        fleet.directory.withdraw_owner(target)
        fleet.submit(Request("q2", rng.integers(0, 64, 14), 3))
        fleet.run()
        assert fleet.routing_decisions["remote_affinity"] == 1

    def test_fleet_drain_inheritance_rides_the_fabric(self):
        """The PR-16 drain test, fabric edition: the retiree's trie
        crosses to the survivor as acked K_CHAIN messages (counted,
        metered), the directory learns the adopter, and the heir
        request promotes remotely-adopted host blocks — visible in the
        remote-vs-local tier-hit split."""
        from kubeshare_tpu.serving import (EngineConfig, ReplicaFleet,
                                           Request, flatten_metrics,
                                           metric_value)
        from kubeshare_tpu.serving.fabric import LoopbackTransport

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        fleet = ReplicaFleet(
            params, config,
            EngineConfig(num_slots=3, block_size=4, num_blocks=21,
                         max_request_len=48, prefill_chunk=8),
            replicas=2, shared_tier_bytes=1 << 20,
            fabric=LoopbackTransport(), fabric_ttl_ticks=8)
        fleet.warmup()
        rng = np.random.default_rng(11)
        shared = rng.integers(0, 64, 16)

        def req(rid):
            return Request(rid, np.concatenate(
                [shared, rng.integers(0, 64, 4)]), 4)

        fleet.submit(req("seed"))
        fleet.run()
        owner = fleet.owner_of("seed")
        survivor = [h for h in fleet.replicas if h.name != owner][0]
        assert survivor.engine.prefix_match_len(shared) == 0
        fleet.drain(owner)
        fleet.run()
        assert fleet._handle(owner).state == "retired"
        assert survivor.engine.prefix_match_len(shared) >= 16
        assert fleet.fabric_adopted_tokens > 0
        assert len(fleet.directory) > 0
        # the retiree's endpoint is gone; nothing is left in flight
        assert owner not in fleet._endpoints
        fleet.submit(req("heir"))
        fleet.run()
        assert fleet.owner_of("heir") == survivor.name
        flat = flatten_metrics(fleet.collect_metrics())
        delivered = metric_value(
            flat, "kubeshare_serving_fabric_messages_total",
            kind="chain", outcome="delivered")
        sent = metric_value(
            flat, "kubeshare_serving_fabric_messages_total",
            kind="chain", outcome="sent")
        assert delivered > 0 and delivered == sent
        assert metric_value(
            flat, "kubeshare_serving_fabric_bytes_total") > 0
        assert metric_value(
            flat, "kubeshare_serving_fabric_chain_tokens_adopted_total"
        ) == fleet.fabric_adopted_tokens
        # the heir's promotion is charged to the REMOTE origin bucket
        assert metric_value(
            flat, "kubeshare_serving_tier_hit_origin_requests_total",
            origin="remote") >= 1

    def test_disagg_tickets_ride_the_fabric_bit_exact(self):
        """Handoff tickets as fabric messages: the split-pool router
        with a loopback fabric emits EXACTLY the monolithic streams —
        greedy and sampled — and every ticket is acked (delivered ==
        sent, nothing in flight at drain)."""
        from kubeshare_tpu.serving import (DisaggRouter, EngineConfig,
                                           Request, ServingEngine,
                                           flatten_metrics,
                                           metric_value)
        from kubeshare_tpu.serving.fabric import LoopbackTransport

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)

        def reqs():
            return [Request(
                f"r{i}", np.arange(3 + i * 2) % 60, 8,
                temperature=(0.0 if i % 2 else 0.7),
                rng=(None if i % 2 else jax.random.PRNGKey(100 + i)))
                for i in range(5)]

        mono = ServingEngine(params, config, EngineConfig(
            num_slots=3, block_size=4, num_blocks=41,
            max_request_len=48, prefill_chunk=8, mixed=False))
        for r in reqs():
            mono.submit(r)
        want = {rid: res.tokens for rid, res in mono.run().items()}
        router = DisaggRouter(
            params, config,
            EngineConfig(num_slots=2, block_size=4, num_blocks=17,
                         max_request_len=48, prefill_chunk=8,
                         mixed=False),
            EngineConfig(num_slots=3, block_size=4, num_blocks=25,
                         max_request_len=48, prefill_chunk=8,
                         mixed=False),
            fabric=LoopbackTransport(), fabric_ttl_ticks=8)
        for r in reqs():
            router.submit(r)
        got = {rid: res.tokens for rid, res in router.run().items()}
        assert got == want
        assert router._fabric_inflight == {}
        assert router._fabric_arrivals == []
        flat = flatten_metrics(router.collect_metrics())
        sent = metric_value(flat,
                            "kubeshare_serving_fabric_messages_total",
                            kind="ticket", outcome="sent")
        assert sent == 5
        assert metric_value(flat,
                            "kubeshare_serving_fabric_messages_total",
                            kind="ticket", outcome="delivered") == sent

    def test_prefix_store_export_serve_fetch(self, tmp_path):
        """The cross-process promotion path's parts: export a
        disk/host-resident trie to a store file, serve it over TCP
        from a jax-free child process, fetch a chain back byte
        identical, and adopt it into a COLD engine whose next request
        is a tier hit instead of a re-prefill."""
        from kubeshare_tpu.serving import (EngineConfig, PrefixStoreClient,
                                           Request, ServingEngine,
                                           export_prefix_store,
                                           load_prefix_store,
                                           serve_prefix_store,
                                           wire_block_bytes)
        from kubeshare_tpu.serving.fabric import (prefix_fabric_key,
                                                  unpack_prefix_blocks)
        from kubeshare_tpu.serving.kv_tier import adopt_into

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        full_wire = wire_block_bytes(4, config.n_layers, config.kv_heads,
                                     4, config.head_dim, 4)

        def engine(**over):
            kw = dict(num_slots=1, block_size=4, num_blocks=13,
                      max_request_len=32, prefill_chunk=8,
                      host_tier_bytes=1 << 20)
            kw.update(over)
            return ServingEngine(params, config, EngineConfig(**kw))

        rng = np.random.default_rng(11)
        shared = rng.integers(0, 64, 13)
        warm = engine()
        for rid, prompt in (("r0", shared),
                            ("f1", rng.integers(0, 64, 29)),
                            ("f2", rng.integers(0, 64, 29))):
            warm.submit(Request(rid, prompt, 3))
            warm.run()
            warm.pop_finished()

        def payload_of(node):
            if node.host_key is not None:
                e = warm.host_tier.probe(node.host_key)
                return None if e is None else e.payload
            if node.disk_key is not None:
                return warm.disk_tier.read(node.disk_key)
            if node.block is not None and node.block >= 0:
                # live exporter: serialize device rows on the fly
                return warm._read_block_payload(node)
            return None

        path = str(tmp_path / "prefixes.kvps")
        manifest = export_prefix_store(warm.prefix_index, payload_of,
                                       path)
        assert len(manifest) > 0
        store = load_prefix_store(path)
        assert set(store) == {k for k, _ in manifest}
        # serve over real TCP from a CHILD PROCESS on a plain Python +
        # numpy footprint: stub packages stand in for the three
        # __init__ files, so importing the fabric never runs the serving
        # package's own (and jax behind it) — asserted there
        import os
        import subprocess
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        child = (
            "import sys, types\n"
            "root, store = sys.argv[1], sys.argv[2]\n"
            "for name in ('kubeshare_tpu', 'kubeshare_tpu.utils',\n"
            "             'kubeshare_tpu.serving'):\n"
            "    pkg = types.ModuleType(name)\n"
            "    pkg.__path__ = [root + '/' + name.replace('.', '/')]\n"
            "    sys.modules[name] = pkg\n"
            "from kubeshare_tpu.serving import fabric\n"
            "assert 'jax' not in sys.modules, 'store server pulled in jax'\n"
            "fabric.serve_prefix_store(store)\n")
        proc = subprocess.Popen([sys.executable, "-c", child, root, path],
                                stdout=subprocess.PIPE, text=True)
        line = proc.stdout.readline()
        assert line.startswith("PORT "), f"store never bound: {line!r}"
        port = int(line.split()[1])
        key, token_len = max(manifest, key=lambda kv: kv[1])
        client = PrefixStoreClient(port)
        chain = client.fetch(key)
        assert chain and unpack_prefix_blocks(store[key])[-1][1] \
            == chain[-1][1]
        assert client.fetch(b"\x00" * 16) == []  # unknown key: empty
        client.close()
        assert proc.wait(timeout=10) == 0
        # adopt the fetched chain into a COLD engine: its next request
        # over the same prefix is a tier hit, not a re-prefill
        cold = engine()
        toks, _ = chain[-1]
        assert cold.prefix_match_len(toks) == 0
        for ctoks, payload in chain:
            adopt_into(cold.host_tier, cold.prefix_index, ctoks,
                       payload, None, origin="remote")
        assert cold.prefix_match_len(toks) == len(toks)
        assert prefix_fabric_key(toks) == key
