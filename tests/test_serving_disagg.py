"""Serving subsystem tests: disaggregated prefill and decode pools.

The contract is the one ``tests/test_serving.py`` states: the paged pool +
continuous-batching engine emit EXACTLY the token stream the dense-cache
reference paths emit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models.transformer import transformer_init

from serving_helpers import _cyclic_params, _small_config

pytestmark = pytest.mark.serving


class TestDisagg:
    """Tentpole contract: the split-pool disaggregated engine (prefill
    pool + decode pool + KV-chain migration over the tier wire format)
    emits EXACTLY the monolithic engine's streams — greedy and sampled,
    across GQA/windowed/MoE, speculation on or off, across preemption —
    with zero recompiles after both pools warm up."""

    MONO = dict(num_slots=3, block_size=4, num_blocks=41,
                max_request_len=48, prefill_chunk=8, mixed=False)
    PREFILL = dict(num_slots=2, block_size=4, num_blocks=17,
                   max_request_len=48, prefill_chunk=8, mixed=False)
    DECODE = dict(num_slots=3, block_size=4, num_blocks=25,
                  max_request_len=48, prefill_chunk=8, mixed=False)

    def _mono(self, params, config, tenants=None, **overrides):
        from kubeshare_tpu.serving import EngineConfig, ServingEngine

        kwargs = dict(self.MONO)
        kwargs.update(overrides)
        return ServingEngine(params, config, EngineConfig(**kwargs),
                             tenants=tenants)

    def _router(self, params, config, prefill=None, decode=None,
                shared=None, **kwargs):
        from kubeshare_tpu.serving import DisaggRouter, EngineConfig

        p = dict(self.PREFILL)
        p.update(prefill or {})
        p.update(shared or {})
        d = dict(self.DECODE)
        d.update(decode or {})
        d.update(shared or {})
        return DisaggRouter(params, config, EngineConfig(**p),
                            EngineConfig(**d), **kwargs)

    def _streams(self, engine, reqs):
        from kubeshare_tpu.serving import Request

        for req in reqs:
            engine.submit(Request(**req))
        return {rid: r.tokens for rid, r in engine.run().items()}

    def test_streams_bit_exact_disagg_vs_monolithic_across_configs(self):
        """Disagg vs monolithic, token for token: the migrated slot is
        indistinguishable from one that finished prefill in place.
        Prompt lengths deliberately off block-size multiples, so every
        chain ships a sub-block partial tail frame; the GQA case adds
        SAMPLED lanes (the per-request key schedule must survive the
        handoff: emission k decode-side consumes exactly the key the
        monolithic engine's emission k would)."""
        cases = {
            "gqa_rope": dict(n_kv_heads=2, positional="rope"),
            "windowed": dict(attention_window=6),
            "moe": dict(moe_every=2, moe_num_experts=4, moe_top_k=2),
        }
        rng = np.random.default_rng(61)
        reqs = [
            dict(rid="long", prompt=rng.integers(0, 64, 29),
                 max_new_tokens=6),
            dict(rid="s0", prompt=rng.integers(0, 64, 5),
                 max_new_tokens=8),
            dict(rid="s1", prompt=rng.integers(0, 64, 13),
                 max_new_tokens=4),
        ]
        sampled = [
            dict(rid="samp", prompt=rng.integers(0, 64, 11),
                 max_new_tokens=7, temperature=0.8,
                 rng=jax.random.PRNGKey(62)),
            dict(rid="samp2", prompt=rng.integers(0, 64, 21),
                 max_new_tokens=5, temperature=1.1,
                 rng=jax.random.PRNGKey(63)),
        ]
        for name, extra in cases.items():
            config = _small_config(**extra)
            params = transformer_init(jax.random.PRNGKey(0), config)
            workload = reqs + (sampled if name == "gqa_rope" else [])
            shared = (dict(top_k=10, top_p=0.95)
                      if name == "gqa_rope" else {})
            mono = self._mono(params, config, **shared)
            router = self._router(params, config, shared=shared)
            mono.warmup()
            router.warmup()
            base = router.compile_counts()
            want = self._streams(mono, workload)
            got = self._streams(router, workload)
            assert got == want, name
            # every request crossed the wire exactly once...
            assert router.migrator.migrations == len(workload), name
            assert router.migrator.delivered == len(workload), name
            assert router.migrator.migrated_bytes > 0, name
            # ...each pool ran ONLY its phase's dispatches...
            assert router.prefill.decode_steps == 0, name
            assert router.decode.prefill_chunks == 0, name
            # ...and nothing recompiled after warmup
            assert router.compile_counts() == base, name

    def test_chain_wire_roundtrip_bfloat16_partial_tail(self):
        """The migration envelope: length-prefixed pack_block frames
        inside a pack_chain header, bfloat16 slabs, last frame a
        sub-block partial (stale tail rows ride along) — byte-identical
        round-trip, loud on foreign magic / version / zero frames."""
        from kubeshare_tpu.serving import (KV_CHAIN_VERSION, pack_block,
                                           pack_chain, unpack_block,
                                           unpack_chain)

        dtype = np.dtype(jnp.bfloat16.dtype)
        rng = np.random.default_rng(7)
        runs = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10]]  # partial tail
        slabs = [
            (rng.standard_normal((2, 2, 4, 8)).astype(dtype),
             rng.standard_normal((2, 2, 4, 8)).astype(dtype))
            for _ in runs]
        frames = [pack_block(toks, k, v)
                  for toks, (k, v) in zip(runs, slabs)]
        buf = pack_chain(frames)
        assert buf[:4] == b"KVCH"
        back = unpack_chain(buf)
        assert back == frames
        for toks, (k, v), frame in zip(runs, slabs, back):
            t2, k2, v2 = unpack_block(frame)
            assert list(t2) == toks
            assert k2.dtype == dtype and v2.dtype == dtype
            assert k2.tobytes() == k.tobytes()
            assert v2.tobytes() == v.tobytes()
        # loud failures: bad magic, bad version, empty chain
        with pytest.raises(ValueError, match="chain magic"):
            unpack_chain(b"XXCH" + buf[4:])
        bad = bytearray(buf)
        bad[4] = KV_CHAIN_VERSION + 1
        with pytest.raises(ValueError, match="chain version"):
            unpack_chain(bytes(bad))
        with pytest.raises(ValueError, match="at least one"):
            pack_chain([])

    def test_speculative_drafter_state_survives_handoff(self):
        """Spec-on disagg: the drafter's trie-continuation hint is
        captured at prefill admission, rides the ticket, and is
        reinstalled decode-side — so a cache-hit lane drafts (and
        accepts) after migration, and the stream still matches the
        monolithic spec engine token for token."""
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = _cyclic_params(config)
        phrase = [7, 11, 19, 7, 11, 19, 7, 11, 19, 7, 11, 19]
        full = np.asarray(phrase + [23, 29, 23, 29], np.int32)
        head = np.asarray(phrase[:8], np.int32)  # prefix of `full`

        def drive(eng):
            eng.submit(Request("warm", full, 4))
            eng.run()
            eng.submit(Request("b", head, 8))
            return eng.run()["b"].tokens

        mono = self._mono(params, config, speculative=True)
        mono.warmup()
        want = drive(mono)

        router = self._router(params, config,
                              shared=dict(speculative=True))
        router.warmup()
        base = router.compile_counts()
        tickets = []
        orig = router.migrator.pack

        def spy(engine, slot):
            ticket = orig(engine, slot)
            tickets.append(ticket)
            return ticket

        router.migrator.pack = spy
        got = drive(router)
        assert got == want
        assert router.compile_counts() == base
        # the cache-hit lane's ticket carried prompt + continuation
        assert tickets[1].hint is not None
        assert tickets[1].hint[:len(head)] == list(head)
        assert len(tickets[1].hint) > len(head)
        # and the rebuilt drafter actually drafted/accepted post-handoff
        assert sum(router.decode.spec_drafted.values()) >= 1
        assert sum(router.decode.spec_accepted.values()) >= 1

    def test_preemption_mid_migration_bit_exact(self):
        """A Guarantee ticket the decode pool cannot place preempts an
        Opportunistic decode slot; the victim's resume routes BACK
        through the prefill pool (re-prefill where prefill runs) and
        re-migrates — every stream still token-for-token identical to
        the monolithic engine, with zero recompiles."""
        from kubeshare_tpu.serving import (QOS_OPPORTUNISTIC, Request,
                                           TenantRegistry, TenantSpec)

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        tenants = TenantRegistry([
            TenantSpec("gold"),
            TenantSpec("batch", qos_class=QOS_OPPORTUNISTIC),
        ])
        rng = np.random.default_rng(5)
        v0p, v1p, gp = (rng.integers(0, 64, 8) for _ in range(3))

        def drive(eng, is_router):
            eng.submit(Request("v0", v0p, 24, tenant="batch"))
            eng.submit(Request("v1", v1p, 24, tenant="batch"))
            if is_router:  # both victims resident decode-side first
                while eng.migrator.delivered < 2:
                    eng.step()
            else:
                for _ in range(4):
                    eng.step()
            eng.submit(Request("g", gp, 6, tenant="gold",
                               temperature=0.9,
                               rng=jax.random.PRNGKey(77)))
            return {rid: r.tokens for rid, r in eng.run().items()}

        mono = self._mono(params, config, tenants=tenants)
        mono.warmup()
        want = drive(mono, False)

        # decode pool sized so the two victims fill it exactly
        router = self._router(params, config,
                              decode=dict(num_slots=2, num_blocks=17),
                              tenants=tenants)
        router.warmup()
        base = router.compile_counts()
        got = drive(router, True)
        assert got == want
        assert router.compile_counts() == base
        assert router.decode.preemptions.get("batch", 0) >= 1
        # the victim re-prefilled and re-migrated: 3 requests, 4 chains
        assert router.migrator.migrations >= 4
        assert router.migrator.delivered == router.migrator.migrations

    def test_shared_tier_is_cross_pool_cache_bus_and_meters_ledger(self):
        """One host tier under both tries: a chain the DECODE pool
        demoted (prompt + generated rows the prefill pool never held)
        is adopted into the PREFILL trie as host mirrors, and a later
        request extending that stream tier-promotes prefill-side.  The
        ledger hook sees every demote/promote/migrate byte — migrate
        bytes exactly matching the migrator's counter."""
        from kubeshare_tpu.serving import Request, ServingEngine

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        ledger = []
        router = self._router(
            params, config,
            decode=dict(num_slots=2, num_blocks=13),
            shared_tier_bytes=1 << 20,
            ledger_hook=lambda nbytes, kind: ledger.append((kind, nbytes)))
        router.warmup()
        base = router.compile_counts()
        rng = np.random.default_rng(9)
        pA = rng.integers(0, 64, 12)
        router.submit(Request("a0", pA, 6))
        a0 = router.run()["a0"].tokens
        # flood: drains the decode pool's cached chains into the shared
        # tier; the generated-row blocks mirror into the prefill trie
        for i in range(6):
            router.submit(Request(f"o{i}", rng.integers(0, 64, 12), 6))
        router.run()
        ext = np.concatenate([pA, np.asarray(a0, np.int32)])
        router.submit(Request("ext", ext, 4))
        got = router.run()["ext"].tokens
        assert router.compile_counts() == base
        # rows 12.. of `ext` exist ONLY via the decode pool's demoted
        # chain: serving them from the prefill pool proves the bus
        assert router.prefill.tier_hit_requests >= 1
        mono = self._mono(params, config)
        mono.warmup()
        mono.submit(Request("ext", ext, 4))
        assert got == mono.run()["ext"].tokens
        kinds = {}
        for kind, nbytes in ledger:
            assert nbytes > 0
            kinds[kind] = kinds.get(kind, 0) + nbytes
        assert set(kinds) == {"demote", "promote", "migrate"}
        assert kinds["migrate"] == router.migrator.migrated_bytes

    def test_migration_metrics_and_pool_labels(self):
        """The router's merged metrics plane: migration counters and
        the stall histogram are present, per-pool families carry the
        ``pool`` label both ways, and the monolithic engine's families
        stay UNLABELED (dashboards keyed on the old series survive)."""
        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        router = self._router(params, config)
        router.warmup()
        rng = np.random.default_rng(21)
        reqs = [dict(rid=f"r{i}", prompt=rng.integers(0, 64, 9),
                     max_new_tokens=4) for i in range(3)]
        self._streams(router, reqs)
        fams = {f.name: f for f in router.collect_metrics()}

        mig = fams["kubeshare_serving_migrations_total"]
        stages = {s.labels["stage"]: s.value for s in mig.samples}
        assert stages == {"packed": 3.0, "delivered": 3.0}
        assert fams["kubeshare_serving_migrated_bytes_total"] \
            .samples[0].value > 0
        stall = fams["kubeshare_serving_migration_stall_seconds"]
        counts = [s for s in stall.samples if s.name.endswith("_count")]
        assert counts and counts[0].value == 3.0

        disp = fams["kubeshare_serving_dispatches_total"]
        pools = {s.labels.get("pool") for s in disp.samples}
        assert pools == {"prefill", "decode"}
        ttft = fams["kubeshare_serving_ttft_seconds"]
        assert {"prefill", "decode"} <= {
            s.labels.get("pool") for s in ttft.samples}

        mono = self._mono(params, config)
        mono.warmup()
        self._streams(mono, reqs)
        mono_disp = {f.name: f for f in mono.collect_metrics()}[
            "kubeshare_serving_dispatches_total"]
        assert all("pool" not in s.labels for s in mono_disp.samples)

    def test_virtual_multislice_topology_places_pools_apart(self):
        """virtual_multislice topology: the pools land on devices from
        slice 0 and slice 1 of the dryrun 2-slice mesh (distinct CPU
        devices under conftest's 8-device virtual topology), the KV
        chain crosses that boundary, and streams stay bit-exact."""
        from kubeshare_tpu.constants import (ENV_MEGASCALE_NUM_SLICES,
                                             ENV_MEGASCALE_SLICE_ID)
        from kubeshare_tpu.parallel.distributed import \
            multislice_spec_from_env
        from kubeshare_tpu.serving import DisaggTopology

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        ms = multislice_spec_from_env({ENV_MEGASCALE_NUM_SLICES: "2",
                                       ENV_MEGASCALE_SLICE_ID: "0"})
        router = self._router(
            params, config,
            topology=DisaggTopology("virtual_multislice", ms))
        router.warmup()
        assert (router.prefill.pool.k.devices()
                != router.decode.pool.k.devices())
        rng = np.random.default_rng(51)
        reqs = [dict(rid="a", prompt=rng.integers(0, 64, 14),
                     max_new_tokens=5),
                dict(rid="b", prompt=rng.integers(0, 64, 7),
                     max_new_tokens=6)]
        mono = self._mono(params, config)
        mono.warmup()
        want = self._streams(mono, reqs)
        assert self._streams(router, reqs) == want
        assert router.migrator.delivered == 2

    def test_loud_misconfiguration(self):
        """The failure modes that must crash, not corrupt: geometry
        mismatch between pools, direct submit into a decode pool,
        mixed batching on a single-phase pool, and a request the decode
        pool could never hold (rejected BEFORE burning prefill work)."""
        from kubeshare_tpu.serving import (BlockExhausted, DecodePool,
                                           DisaggRouter, EngineConfig,
                                           Request, ServingEngine)

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        with pytest.raises(ValueError, match="disagree on block_size"):
            DisaggRouter(params, config,
                         EngineConfig(**self.PREFILL),
                         EngineConfig(**{**self.DECODE,
                                         "block_size": 8}))
        with pytest.raises(ValueError, match="mixed"):
            ServingEngine(params, config, EngineConfig(
                **{**self.PREFILL, "mixed": True,
                   "pool_role": "prefill"}))
        decode = DecodePool(params, config, EngineConfig(**self.DECODE))
        with pytest.raises(RuntimeError, match="admit_migrated"):
            decode.submit(Request("r", np.arange(4, dtype=np.int32), 2))
        router = self._router(params, config,
                              decode=dict(num_slots=2, num_blocks=5))
        with pytest.raises(BlockExhausted, match="NEVER migrate"):
            router.submit(Request("big", np.arange(20, dtype=np.int32),
                                  20))
