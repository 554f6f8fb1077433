"""Serving subsystem tests: the two device loops (K-step decode, verify in
the loop).

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


class TestDeviceLoop:
    """Tentpole contract: ``steps_per_launch=K`` compiles ONE device-
    resident loop running up to K scheduler iterations of the paged
    decode span — sampling, stop/budget detection and the emitted-token
    ring all on device, early exit the moment any lane deactivates —
    and emits EXACTLY the K=1 streams, greedy and sampled, across
    GQA/windowed/MoE, preemption-resume and retire, with zero new
    compiled shapes after warmup."""

    def _pair(self, params, config, k, **overrides):
        from kubeshare_tpu.serving import EngineConfig, ServingEngine

        kwargs = dict(num_slots=3, block_size=4, num_blocks=41,
                      max_request_len=48, prefill_chunk=8,
                      steps_per_launch=k)
        kwargs.update(overrides)
        return ServingEngine(params, config, EngineConfig(**kwargs))

    def _streams(self, engine, reqs):
        from kubeshare_tpu.serving import Request

        for req in reqs:
            engine.submit(Request(**req))
        return {rid: r.tokens for rid, r in engine.run().items()}

    def test_streams_bit_exact_loop_on_vs_off_across_configs(self):
        """Loop on vs off, token for token, same workload: lanes at
        staggered budgets so launches exit early at different units,
        admissions landing between launches.  The GQA case carries
        SAMPLED lanes (the flat key index u*span+j must hand emission k
        exactly the key the K=1 re-marshaled dispatches would)."""
        cases = {
            "gqa_rope": dict(n_kv_heads=2, positional="rope"),
            "windowed": dict(attention_window=6),
            "moe": dict(moe_every=2, moe_num_experts=4, moe_top_k=2),
        }
        rng = np.random.default_rng(71)
        reqs = [
            dict(rid="long", prompt=rng.integers(0, 64, 29),
                 max_new_tokens=14),
            dict(rid="s0", prompt=rng.integers(0, 64, 5),
                 max_new_tokens=9),
            dict(rid="s1", prompt=rng.integers(0, 64, 13),
                 max_new_tokens=4),
            dict(rid="long2", prompt=rng.integers(0, 64, 21),
                 max_new_tokens=11),
        ]
        sampled = [
            dict(rid="samp", prompt=rng.integers(0, 64, 13),
                 max_new_tokens=12, temperature=0.8,
                 rng=jax.random.PRNGKey(72)),
            dict(rid="samp2", prompt=rng.integers(0, 64, 11),
                 max_new_tokens=7, temperature=1.1,
                 rng=jax.random.PRNGKey(73)),
        ]
        for name, extra in cases.items():
            config = _small_config(**extra)
            params = transformer_init(jax.random.PRNGKey(0), config)
            workload = reqs + (sampled if name == "gqa_rope" else [])
            kwargs = (dict(top_k=10, top_p=0.95)
                      if name == "gqa_rope" else {})
            on = self._pair(params, config, 4, **kwargs)
            off = self._pair(params, config, 1, **kwargs)
            got = self._streams(on, workload)
            want = self._streams(off, workload)
            assert got == want, name
            # the loop actually ran (and the control arm has none)
            assert on.loop_launches > 0, name
            assert on.loop_units > 0, name
            assert off.loop_launches == 0, name

    def test_planner_invocations_drop_on_decode_heavy_trace(self):
        """The point of the PR: on a decode-dominated trace the host
        planner runs ~K x fewer times per emitted token (each launch
        covers up to K iterations the K=1 engine plans one by one)."""
        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(74)
        reqs = [dict(rid="d", prompt=rng.integers(0, 64, 5),
                     max_new_tokens=32)]
        counts = {}
        for k in (1, 4):
            engine = self._pair(params, config, k)
            streams = self._streams(engine, list(reqs))
            assert len(streams["d"]) == 32
            counts[k] = engine.host_planner_invocations
            # the counter flows through the metrics plane
            sample = [sm for f in engine.collect_metrics()
                      if f.name ==
                      "kubeshare_serving_host_planner_invocations_total"
                      for sm in f.samples]
            assert sample and sample[0].value == counts[k]
        # 32 tokens / span 4 = 8 decode plans at K=1 vs 2 launches at
        # K=4; prefill + drain plans are common to both arms
        assert counts[4] < counts[1]
        assert counts[1] - counts[4] >= 4

    def test_mid_scan_preemption_resume_bit_exact(self):
        """A Guarantee admission preempting an Opportunistic lane MID
        FLIGHT under the loop: the in-flight ring is consumed first
        (its accepted tokens are real), the victim retires into the
        prefix cache and resumes emitting EXACTLY its unpreempted
        stream — against the dense greedy oracle."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import (QOS_OPPORTUNISTIC,
                                           EngineConfig, Request,
                                           ServingEngine,
                                           TenantRegistry, TenantSpec)

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        registry = TenantRegistry([
            TenantSpec("gold"),
            TenantSpec("batch", qos_class=QOS_OPPORTUNISTIC),
        ])
        engine = ServingEngine(
            params, config,
            EngineConfig(num_slots=2, block_size=4, num_blocks=13,
                         max_request_len=32, prefill_chunk=8,
                         steps_per_launch=4),
            tenants=registry)
        engine.warmup()
        baseline = engine.compile_counts()
        rng = np.random.default_rng(75)
        # same block geometry as TestQoSPreemption (victim grows to 8
        # blocks, gold needs 6 > 4 free -> preempt) but the victim's
        # 22-token budget OUTLASTS one 16-deep launch (K*span), so gold
        # arrives while a launch is in flight: the preemption consumes
        # that ring first — its accepted tokens are real — then evicts
        p_batch = rng.integers(0, 64, 9)   # 9 + 22 = 31 rows, 8 blocks
        p_gold = rng.integers(0, 64, 18)   # 18 + 6 = 24 rows, 6 blocks
        engine.submit(Request("victim", p_batch, 22, tenant="batch"))
        while True:
            r = engine.result("victim")
            if r.first_token_at is not None and not r.done:
                break
            assert engine.step(), "engine idle before victim decoded"
        engine.submit(Request("gold", p_gold, 6, tenant="gold"))
        out = engine.run()
        assert engine.preemptions.get("batch", 0) >= 1
        assert engine.loop_launches >= 1
        for rid, prompt, new in (("victim", p_batch, 22),
                                 ("gold", p_gold, 6)):
            ref = np.asarray(greedy_decode(
                params, config, jnp.asarray(prompt, jnp.int32)[None],
                new))[0]
            assert out[rid].tokens == list(ref), rid
        assert engine.allocator.blocks_in_use == 0
        assert engine.compile_counts() == baseline

    def test_ring_drained_at_retire(self):
        """A budget ending mid-launch: the device detects it (budget
        check per emission, early exit at the unit boundary), the host
        drains the ring capped at the lane's budget — never a token
        past max_new_tokens, never a dropped one — and the launch
        stops short of its K units."""
        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(76)
        # 10 tokens, span 4, K=4: the sole lane dies at emission 10 of
        # a 16-deep ring -> exit after unit 3 of 4
        engine = self._pair(params, config, 4)
        streams = self._streams(
            engine, [dict(rid="short", prompt=rng.integers(0, 64, 5),
                          max_new_tokens=10)])
        assert len(streams["short"]) == 10
        assert engine.loop_launches >= 1
        # early exit: units actually run < launches * K
        assert engine.loop_units < engine.loop_launches * 4
        assert engine.allocator.blocks_in_use == 0

    def test_zero_recompiles_after_warmup(self):
        """The loop program is warmed once (all-inactive lanes, exits
        at unit 0) and never compiles again — across greedy, sampled,
        early exits and admissions between launches."""
        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = self._pair(params, config, 4, top_k=10, top_p=0.95)
        engine.warmup()
        baseline = engine.compile_counts()
        assert baseline["loop"] >= 1
        rng = np.random.default_rng(77)
        self._streams(engine, [
            dict(rid="a", prompt=rng.integers(0, 64, 9),
                 max_new_tokens=13),
            dict(rid="b", prompt=rng.integers(0, 64, 17),
                 max_new_tokens=6, temperature=0.9,
                 rng=jax.random.PRNGKey(78)),
            dict(rid="c", prompt=rng.integers(0, 64, 5),
                 max_new_tokens=10),
        ])
        assert engine.loop_launches >= 1
        assert engine.compile_counts() == baseline

    def test_config_validation_is_loud(self):
        """Satellite: bad K values and incompatible combos fail at
        construction, not deep in a launch."""
        from kubeshare_tpu.serving import (DisaggRouter, EngineConfig,
                                           ServingEngine)

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        for bad in (0, -1, 3, 6):
            with pytest.raises(ValueError, match="power of two"):
                ServingEngine(params, config, EngineConfig(
                    num_slots=2, block_size=4, num_blocks=13,
                    max_request_len=32, prefill_chunk=8,
                    steps_per_launch=bad))
        with pytest.raises(ValueError, match="never runs decode"):
            ServingEngine(params, config, EngineConfig(
                num_slots=2, block_size=4, num_blocks=13,
                max_request_len=32, prefill_chunk=8, mixed=False,
                pool_role="prefill", steps_per_launch=2))
        shared = dict(block_size=4, max_request_len=32,
                      prefill_chunk=8, mixed=False)
        with pytest.raises(ValueError, match="decode_priority pacing"):
            DisaggRouter(
                params, config,
                EngineConfig(num_slots=2, num_blocks=17, **shared),
                EngineConfig(num_slots=2, num_blocks=17,
                             steps_per_launch=2, **shared),
                decode_priority=2)


class TestSpecLoop:
    """Device residency v2: drafted rounds run INSIDE the device loop —
    each unit drafts via on-device n-gram suffix match, verifies at
    width W and applies acceptance without leaving device — and the
    pending-lane admission ring activates pre-marshaled lanes at span
    boundaries when a lane retires.  The oracle is the K=1 non-loop
    speculative engine: bit-exact streams, greedy and sampled, with
    zero new compiled shapes after warmup."""

    def _engine(self, params, config, k, **overrides):
        from kubeshare_tpu.serving import EngineConfig, ServingEngine

        kwargs = dict(num_slots=3, block_size=4, num_blocks=41,
                      max_request_len=48, prefill_chunk=8,
                      speculative=True, steps_per_launch=k)
        kwargs.update(overrides)
        return ServingEngine(params, config, EngineConfig(**kwargs))

    def _streams(self, engine, reqs):
        from kubeshare_tpu.serving import Request

        for req in reqs:
            engine.submit(Request(**req))
        return {rid: r.tokens for rid, r in engine.run().items()}

    def _spec_reqs(self, n=4, new=10, sampled=()):
        """Repetitive prompts (tiled patterns) so the n-gram drafter
        proposes on every lane and decode rounds go all-drafted —
        the rounds the spec loop exists to absorb."""
        rng = np.random.default_rng(81)
        reqs = []
        for i in range(n):
            pat = rng.integers(0, 64, 4)
            prompt = np.concatenate(
                [np.tile(pat, 3), rng.integers(0, 64, 2)])
            req = dict(rid=f"r{i}", prompt=prompt, max_new_tokens=new)
            if i in sampled:
                req.update(temperature=0.8,
                           rng=jax.random.PRNGKey(82 + i))
            reqs.append(req)
        return reqs

    def test_streams_bit_exact_spec_loop_on_vs_off(self):
        """Loop-on vs loop-off, token for token, greedy AND sampled,
        across GQA and windowed attention — the bit-exactness argument
        (verification is exact-match against the engine's own pick
        policy keyed by emission number, so the device drafter's
        scheduling-only differences from the host drafter can change
        acceptance RATE, never a stream) made empirical."""
        cases = {
            "gqa_rope": dict(n_kv_heads=2, positional="rope"),
            "windowed": dict(attention_window=6),
        }
        for name, extra in cases.items():
            config = _small_config(**extra)
            params = _cyclic_params(config)
            sampled = (1, 2) if name == "gqa_rope" else ()
            kwargs = (dict(top_k=10, top_p=0.95)
                      if name == "gqa_rope" else {})
            workload = self._spec_reqs(n=3, new=12, sampled=sampled)
            on = self._engine(params, config, 4, **kwargs)
            off = self._engine(params, config, 1, **kwargs)
            got = self._streams(on, list(workload))
            want = self._streams(off, list(workload))
            assert got == want, name
            assert on.spec_loop_launches > 0, name
            assert on.spec_loop_units > 0, name
            assert off.spec_loop_launches == 0, name

    def test_admission_ring_activates_lanes_bit_exact(self):
        """More requests than slots with the ring armed: retiring lanes
        hand their slot to pre-marshaled pending lanes AT SPAN
        BOUNDARIES inside a launch (prefilled ahead, PRNG schedule
        written ahead, key index reset on activation) — and the streams
        still match the ring-off, loop-off engine exactly."""
        config = _small_config()
        params = _cyclic_params(config)
        workload = self._spec_reqs(n=7, new=8, sampled=(2, 5))
        kwargs = dict(top_k=10, top_p=0.95)
        ring = self._engine(params, config, 4, admission_ring=2,
                            **kwargs)
        off = self._engine(params, config, 1, **kwargs)
        got = self._streams(ring, list(workload))
        want = self._streams(off, list(workload))
        assert got == want
        assert ring.spec_loop_launches > 0
        # ring pressure was real: either a staged lane activated inside
        # a launch or a launch exited starving (ring_empty) — both are
        # the ring path, and on this 7-request/3-slot trace at least
        # one of the two must have happened
        assert (ring.loop_exit_reasons["ring_empty"] > 0
                or ring.spec_loop_units > ring.spec_loop_launches)
        assert ring.allocator.blocks_in_use == 0
        assert ring._ring_staged == []

    def test_exit_reason_and_depth_metrics(self):
        """Satellite: every launch lands exactly one exit-reason count,
        and the realized-depth summary reports unit depth directly —
        sum = units, count = launches — so a reader of the metrics
        endpoint gets fusion depth without dividing counters."""
        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = self._engine(params, config, 4, admission_ring=2)
        self._streams(engine, self._spec_reqs(n=6, new=8))
        launches = engine.loop_launches + engine.spec_loop_launches
        units = engine.loop_units + engine.spec_loop_units
        assert launches > 0
        assert sum(engine.loop_exit_reasons.values()) == launches
        assert set(engine.loop_exit_reasons) == {
            "retire", "budget", "stop", "redraft", "ring_empty"}
        assert engine.loop_depth_count == launches
        assert engine.loop_depth_sum == units
        fams = {f.name: f for f in engine.collect_metrics()}
        reasons = fams["kubeshare_serving_loop_exit_reason_total"]
        by_reason = {s.labels["reason"]: s.value for s in reasons.samples}
        assert by_reason == {k: v for k, v
                             in engine.loop_exit_reasons.items()}
        depth = fams["kubeshare_serving_loop_realized_depth"]
        vals = {s.name.rsplit("_", 1)[-1]: s.value
                for s in depth.samples}
        assert vals["sum"] == units
        assert vals["count"] == launches
        su = fams["kubeshare_serving_spec_loop_units_total"]
        assert sum(s.value for s in su.samples) == engine.spec_loop_units

    def test_zero_recompiles_after_warmup(self):
        """The verify-in-loop program (and its ring variant) is warmed
        once per loop depth and never compiles again — greedy, sampled,
        redraft exits, ring activations, admissions between launches."""
        config = _small_config()
        params = _cyclic_params(config)
        engine = self._engine(params, config, 4, admission_ring=2,
                              top_k=10, top_p=0.95)
        engine.warmup()
        baseline = engine.compile_counts()
        assert baseline["spec_loop"] >= 1
        self._streams(engine, self._spec_reqs(n=6, new=9, sampled=(1, 4)))
        assert engine.spec_loop_launches > 0
        assert engine.compile_counts() == baseline

    def test_config_validation_is_loud(self):
        from kubeshare_tpu.serving import EngineConfig, ServingEngine

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        geo = dict(num_slots=2, block_size=4, num_blocks=13,
                   max_request_len=32, prefill_chunk=8)
        with pytest.raises(ValueError, match="admission_ring"):
            ServingEngine(params, config, EngineConfig(
                admission_ring=-1, **geo))
        # the ring rides the verify-in-loop launch: it needs
        # speculation, a real loop depth, and a decode-capable pool
        for bad in (dict(admission_ring=2),
                    dict(admission_ring=2, speculative=True),
                    dict(admission_ring=2, speculative=True,
                         steps_per_launch=2, mixed=False,
                         pool_role="decode")):
            with pytest.raises(ValueError, match="admission_ring"):
                ServingEngine(params, config,
                              EngineConfig(**{**geo, **bad}))
