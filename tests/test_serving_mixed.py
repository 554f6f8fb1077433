"""Serving subsystem tests: mixed prefill + decode batching.

The contract is the one ``tests/test_serving.py`` states: the paged pool +
continuous-batching engine emit EXACTLY the token stream the dense-cache
reference paths emit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models.transformer import transformer_init

from serving_helpers import _engine, _small_config

pytestmark = pytest.mark.serving


class TestMixedBatching:
    """Tentpole contract: the fused mixed step (one budget-bounded
    prefill chunk riding the decode dispatch) emits EXACTLY the
    streams the either/or scheduler emits — across GQA/windowed/MoE,
    greedy and sampled, with prefix-cache CoW and QoS preemption in
    play — and adds zero compiled shapes after warmup."""

    def _pair(self, params, config, mixed, **overrides):
        from kubeshare_tpu.serving import EngineConfig, ServingEngine

        kwargs = dict(num_slots=3, block_size=4, num_blocks=41,
                      max_request_len=48, prefill_chunk=8, mixed=mixed)
        kwargs.update(overrides)
        return ServingEngine(params, config, EngineConfig(**kwargs))

    def _streams(self, engine, reqs):
        from kubeshare_tpu.serving import Request

        for req in reqs:
            engine.submit(Request(**req))
        return {rid: r.tokens for rid, r in engine.run().items()}

    def test_streams_bit_exact_mixed_on_vs_off_across_configs(self):
        """Mixed on vs off, token for token, same workload: long
        multi-chunk prompts prefilling while other lanes decode —
        exactly the coexistence the fused step handles.  The GQA case
        carries SAMPLED lanes too (the key schedule must survive
        fusion: lanes riding mixed dispatches consume exactly the keys
        the split dispatches would)."""
        cases = {
            "gqa_rope": dict(n_kv_heads=2, positional="rope"),
            "windowed": dict(attention_window=6),
            "moe": dict(moe_every=2, moe_num_experts=4, moe_top_k=2),
        }
        rng = np.random.default_rng(31)
        reqs = [
            dict(rid="long", prompt=rng.integers(0, 64, 29),
                 max_new_tokens=6),
            dict(rid="s0", prompt=rng.integers(0, 64, 5),
                 max_new_tokens=8),
            dict(rid="s1", prompt=rng.integers(0, 64, 13),
                 max_new_tokens=4),
            dict(rid="long2", prompt=rng.integers(0, 64, 21),
                 max_new_tokens=5),
        ]
        sampled = [
            dict(rid="samp_long", prompt=rng.integers(0, 64, 29),
                 max_new_tokens=6, temperature=0.8,
                 rng=jax.random.PRNGKey(41)),
            dict(rid="samp", prompt=rng.integers(0, 64, 13),
                 max_new_tokens=7, temperature=1.1,
                 rng=jax.random.PRNGKey(42)),
        ]
        for name, extra in cases.items():
            config = _small_config(**extra)
            params = transformer_init(jax.random.PRNGKey(0), config)
            workload = reqs + (sampled if name == "gqa_rope" else [])
            kwargs = (dict(top_k=10, top_p=0.95)
                      if name == "gqa_rope" else {})
            on = self._pair(params, config, mixed=True, **kwargs)
            off = self._pair(params, config, mixed=False, **kwargs)
            got = self._streams(on, workload)
            want = self._streams(off, workload)
            assert got == want, name
            # the fused path actually ran (and the control arm didn't)
            assert on.mixed_steps > 0, name
            assert off.mixed_steps == 0, name

    def test_cow_divergence_under_mixed(self):
        """Prefix-cache interaction: a mid-block CoW divergence whose
        prefill rides a mixed dispatch (another lane decoding) must
        not perturb either stream."""
        from kubeshare_tpu.serving import Request

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(33)
        base = rng.integers(0, 64, 21)
        diverge = base.copy()
        diverge[18] = (diverge[18] + 1) % 64  # mid-block divergence
        bg_prompt = rng.integers(0, 64, 13)
        streams = {}
        for mixed in (True, False):
            engine = self._pair(params, config, mixed=mixed)
            engine.submit(Request("warm", base, 2))
            engine.run()  # retires -> base's blocks are in the trie
            engine.submit(Request("bg", bg_prompt, 12))
            for _ in range(4):  # bg reaches decode (same count both
                engine.step()   # arms: no coexistence yet)
            engine.submit(Request("cow", diverge, 6))
            out = engine.run()
            assert engine.cow_copies >= 1
            if mixed:
                assert engine.mixed_steps >= 1
            streams[mixed] = {rid: r.tokens for rid, r in out.items()}
        assert streams[True] == streams[False]

    def test_preemption_resume_under_mixed(self):
        """QoS interaction: cache-backed preemption and bit-exact
        resume survive mixed scheduling (the Guarantee admission's
        prefill fuses with the surviving Opportunistic decode).  The
        zero-new-shapes lock for preemption under a WARMED mixed
        engine lives in TestQoSPreemption (same discipline, 2 slots);
        this test adds the 3-slot shape where fusion runs DURING the
        preemption window."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import (QOS_OPPORTUNISTIC, EngineConfig,
                                           Request, ServingEngine,
                                           TenantRegistry, TenantSpec)

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        registry = TenantRegistry([
            TenantSpec("gold"),
            TenantSpec("batch", qos_class=QOS_OPPORTUNISTIC),
        ])
        engine = ServingEngine(params, config, EngineConfig(
            num_slots=3, block_size=4, num_blocks=13,
            max_request_len=32, prefill_chunk=8), tenants=registry)
        rng = np.random.default_rng(34)
        # victims decode LONG (19 tokens): the pipelined consume runs
        # before anyone is sacrificed, so short victims would simply
        # retire and dodge the preemption this test locks
        p0 = rng.integers(0, 64, 5)   # 5 + 19 = 24 rows -> 6 blocks
        p1 = rng.integers(0, 64, 5)   # 6 more: the 12-block pool is full
        pg = rng.integers(0, 64, 10)  # 10 + 4 = 14 rows -> 4 blocks
        engine.submit(Request("v0", p0, 19, tenant="batch"))
        engine.submit(Request("v1", p1, 19, tenant="batch"))

        def both_decoding():
            slots = [s for s in engine._slots
                     if s.rid in ("v0", "v1")]
            return len(slots) == 2 and all(
                s.state == "decode" and len(s.generated) >= 2
                for s in slots)

        while not both_decoding():
            assert engine.step()
        engine.submit(Request("gold", pg, 4, tenant="gold"))
        out = engine.run()
        assert engine.preemptions.get("batch", 0) >= 1
        assert engine.mixed_steps >= 1  # gold's prefill rode a decode
        for rid, prompt, new in (("v0", p0, 19), ("v1", p1, 19),
                                 ("gold", pg, 4)):
            ref = np.asarray(greedy_decode(
                params, config, jnp.asarray(prompt, jnp.int32)[None],
                new))[0]
            assert out[rid].tokens == list(ref), rid
        assert engine.allocator.blocks_in_use == 0

    def test_mixed_budget_bounds_fused_chunk(self):
        """mixed_prefill_budget bounds the prefill tokens fused per
        step: full-width chunks are sliced to power-of-two pieces at
        or under the budget (never a new compiled shape), and streams
        still match the dense oracle."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = self._pair(params, config, mixed=True,
                            mixed_prefill_budget=4)
        rng = np.random.default_rng(35)
        bg_prompt = rng.integers(0, 64, 5)
        long_prompt = rng.integers(0, 64, 29)
        fused_widths = []
        orig = engine._mixed_step

        def recording(w, pk, pv, p_table, p_start, p_tokens, *rest):
            fused_widths.append(int(p_tokens.shape[1]))
            return orig(w, pk, pv, p_table, p_start, p_tokens, *rest)

        engine._mixed_step = recording
        engine.submit(Request("bg", bg_prompt, 14))
        for _ in range(3):
            engine.step()  # bg decoding before the long prompt lands
        engine.submit(Request("long", long_prompt, 3))
        out = engine.run()
        assert fused_widths and max(fused_widths) <= 4
        for rid, prompt, new in (("bg", bg_prompt, 14),
                                 ("long", long_prompt, 3)):
            ref = np.asarray(greedy_decode(
                params, config, jnp.asarray(prompt, jnp.int32)[None],
                new))[0]
            assert out[rid].tokens == list(ref), rid

    def test_sliced_remainder_stays_bucketed_after_decode_drain(self):
        """Review regression: slicing a wide chunk must leave only
        WARMED bucket widths in the plan (binary decomposition of the
        remainder) — if the decode pool drains mid-slice, the
        remainder dispatches standalone, and a raw width-minus-piece
        remainder (e.g. 12 of a 16-chunk at budget 4) would recompile
        after warmup."""
        from kubeshare_tpu.serving import Request

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = self._pair(params, config, mixed=True, num_slots=2,
                            prefill_chunk=16, mixed_prefill_budget=4)
        engine.warmup()
        baseline = engine.compile_counts()
        rng = np.random.default_rng(39)
        engine.submit(Request("bg", rng.integers(0, 64, 5), 6))
        for _ in range(2):
            engine.step()  # bg decoding, close to its budget
        # 32-token prompt: two 16-wide chunks, sliced at budget 4; bg
        # retires inside the first fused span, stranding the sliced
        # remainder for STANDALONE dispatch
        engine.submit(Request("long", rng.integers(0, 64, 32), 3))
        out = engine.run()
        assert engine.mixed_steps >= 1
        assert len(out["long"].tokens) == 3
        assert engine.compile_counts() == baseline

    def test_prefill_round_robin_rotation(self):
        """Satellite regression: step() used to always advance
        prefill[0], so a many-chunk prompt monopolized prefill ticks
        over later admissions — filling slots must rotate."""
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _engine(params, config, num_slots=2)
        rng = np.random.default_rng(36)
        # two 29-token prompts: 4 chunks each (chunk 8)
        engine.submit(Request("a", rng.integers(0, 64, 29), 2))
        engine.submit(Request("b", rng.integers(0, 64, 29), 2))
        engine.step()  # admits both, runs ONE chunk (slot a)
        engine.step()  # must advance slot b, not a again
        plans = {s.rid: len(s.plan) for s in engine._slots
                 if s.state == "prefill"}
        assert plans == {"a": 3, "b": 3}
        out = engine.run()
        assert all(len(r.tokens) == 2 for r in out.values())

    def test_tbt_histogram_and_mixed_dispatch_counter(self):
        """Satellite: the inter-token-latency histogram rides the
        promtext plane per QoS class, and dispatches_total grows a
        kind="mixed" series consistent with the standalone kinds."""
        from kubeshare_tpu.serving import Request
        from kubeshare_tpu.utils.promtext import encode_families, parse_text

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _engine(params, config)
        rng = np.random.default_rng(37)
        reqs = [("m0", rng.integers(0, 64, 21), 6),
                ("m1", rng.integers(0, 64, 9), 5),
                ("m2", rng.integers(0, 64, 13), 4)]
        for rid, prompt, new in reqs:
            engine.submit(Request(rid, prompt, new))
        engine.run()
        assert engine.mixed_steps >= 1
        samples = {(s.name, tuple(sorted(s.labels.items()))): s.value
                   for s in parse_text(
                       encode_families(engine.collect_metrics()))}
        # every token after a request's first came from a decode span
        # -> one TBT observation each (default tenant = guarantee)
        assert samples[("kubeshare_serving_tbt_seconds_count",
                        (("qos", "guarantee"),))] == sum(
            new - 1 for _, _, new in reqs)
        assert samples[("kubeshare_serving_tbt_seconds_count",
                        (("qos", "opportunistic"),))] == 0
        kinds = {k[1][0][1]: v for k, v in samples.items()
                 if k[0] == "kubeshare_serving_dispatches_total"}
        assert kinds["mixed"] == engine.mixed_steps
        assert kinds["prefill_chunk"] == \
            engine.prefill_chunks - engine.mixed_steps
        assert kinds["decode_span"] == \
            engine.decode_steps - engine.mixed_steps

    def test_dispatch_sync_is_guard_only(self):
        """Satellite regression (host/device overlap): an unguarded
        engine must NOT hard-sync per dispatch (the hot loop pipelines
        one step ahead and reads tokens when consumed); a guarded
        engine still syncs so measured wall time is charged."""
        from kubeshare_tpu.isolation.guard import ExecutionGuard
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        calls = {"n": 0}
        real = jax.block_until_ready

        def counting(x):
            calls["n"] += 1
            return real(x)

        rng = np.random.default_rng(38)
        prompt = rng.integers(0, 64, 9)
        engine = _engine(params, config)
        jax.block_until_ready = counting
        try:
            engine.submit(Request("r0", prompt, 4))
            engine.run()
        finally:
            jax.block_until_ready = real
        assert calls["n"] == 0  # unguarded: fully async dispatches

        class FakeClient:
            def acquire(self, estimate_ms):
                return 1e9

            def release(self, used_ms):
                pass

        from kubeshare_tpu.serving import EngineConfig, ServingEngine

        guard = ExecutionGuard(client=FakeClient(), from_env=False,
                               idle_release_ms=0)
        engine = ServingEngine(params, config, EngineConfig(
            num_slots=3, block_size=4, num_blocks=41,
            max_request_len=48, prefill_chunk=8), guard=guard)
        jax.block_until_ready = counting
        try:
            engine.submit(Request("r1", prompt, 4))
            engine.run()
        finally:
            jax.block_until_ready = real
        assert calls["n"] >= 1  # guarded: every dispatch synced...
        assert guard.total_gated_ms > 0.0  # ...and charged wall time
