"""Serving subsystem tests: the n-gram drafter and speculative decoding.

The contract is the one ``tests/test_serving.py`` states: the paged pool +
continuous-batching engine emit EXACTLY the token stream the dense-cache
reference paths emit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models.transformer import transformer_init

from serving_helpers import _cyclic_params, _engine, _small_config

pytestmark = pytest.mark.serving


class TestDrafter:
    """serving/drafter.py edge cases: the n-gram lookup's contract is
    deliberately small (correctness never depends on it — only the
    acceptance rate does) but its determinism is what the bit-exactness
    tests lean on."""

    def test_empty_history_proposes_nothing(self):
        from kubeshare_tpu.serving import NGramDrafter

        d = NGramDrafter(3)
        assert d.propose(4) == []
        assert d.history == []

    def test_prompt_shorter_than_order_degrades_to_lower_orders(self):
        from kubeshare_tpu.serving import NGramDrafter

        # 2 tokens < order 3: only order 1 has an earlier occurrence
        d = NGramDrafter(3, [7, 7])
        assert d.propose(4) == [7]
        # a single token has NO earlier occurrence at any order
        assert NGramDrafter(3, [7]).propose(4) == []

    def test_most_recent_occurrence_wins(self):
        from kubeshare_tpu.serving import NGramDrafter

        # suffix [1, 2] occurs at i=0 (followed by 9) and i=4
        # (followed by 8): recency wins
        d = NGramDrafter(3, [1, 2, 9, 3, 1, 2, 8, 1, 2])
        assert d.propose(1) == [8]
        assert d.propose(3) == [8, 1, 2]

    def test_longest_suffix_beats_recent_shorter_match(self):
        from kubeshare_tpu.serving import NGramDrafter

        # order-3 suffix [5, 6, 7] matches only at i=0 (follower 9);
        # the order-1 suffix [7] ALSO matches more recently (follower
        # 3) — the longer suffix must win
        d = NGramDrafter(3, [5, 6, 7, 9, 2, 7, 3, 5, 6, 7])
        assert d.propose(1) == [9]

    def test_hint_window_used_only_on_history_miss(self):
        from kubeshare_tpu.serving import NGramDrafter

        d = NGramDrafter(2, [1, 2, 3])
        assert d.propose(2) == []          # no earlier occurrence
        d.hint([1, 2, 3, 4, 5])            # the trie's continuation
        assert d.propose(2) == [4, 5]
        # once the lane's OWN history matches, it wins over the hint
        d.extend([9, 2, 3])
        assert d.propose(1) == [9]

    def test_propose_bounds_and_validation(self):
        from kubeshare_tpu.serving import NGramDrafter

        d = NGramDrafter(1, [3, 5, 3, 5, 3])
        assert d.propose(0) == []
        assert d.propose(2) == [5, 3]      # k caps the draft
        assert d.propose(9) == [5, 3]      # ...and the window ends it
        # a match whose followers run out mid-draft yields what exists:
        # the most recent [4, 4] occurrence has ONE follower
        assert NGramDrafter(2, [4, 4, 4, 4]).propose(2) == [4]
        with pytest.raises(ValueError, match="max_order"):
            NGramDrafter(0)

    def test_engine_truncates_draft_at_remaining_budget(self):
        """A verify round emits at most k + 1 tokens, so the engine
        must cap every draft at remaining - 1: a 3-token budget on a
        loud repeating prompt (draft_len 8) may never dispatch a
        proposal wider than 2 — and the stream still ends exactly at
        max_new_tokens, matching the non-speculative run."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(51)
        p0 = rng.integers(0, 64, 8)
        # extend the prompt with the model's OWN greedy continuation
        # (it settles into a loop): generation provably keeps looping,
        # so the drafter always has a matching suffix to propose from
        cont = np.asarray(greedy_decode(
            params, config, jnp.asarray(p0, jnp.int32)[None], 13))[0]
        prompt = np.concatenate([p0, cont]).astype(np.int32)
        streams = {}
        for spec in (True, False):
            engine = _engine(params, config, speculative=spec,
                             draft_len=8)
            seen_ks = []
            if spec:
                orig = engine._verify_step

                def recording(w, pk, pv, tables, lengths, active,
                              tokens, widths, temps, keys):
                    seen_ks.append(int(np.asarray(widths).max()) - 1)
                    return orig(w, pk, pv, tables, lengths, active,
                                tokens, widths, temps, keys)

                engine._verify_step = recording
            engine.submit(Request("r0", prompt, 3))
            streams[spec] = engine.run()["r0"].tokens
            if spec:
                assert seen_ks, "speculation never engaged"
                assert max(seen_ks) <= 2  # rem - 1 with 3 to go
        assert streams[True] == streams[False]
        assert len(streams[True]) == 3


class TestSpeculative:
    """Tentpole contract: self-drafting speculative decoding emits
    EXACTLY the streams sequential decoding emits — by construction
    (exact-match verification against the target's own picks), across
    attention variants, greedy and sampled, mixed batching on and off,
    and across preemption-resume — while spending fewer target
    dispatches per token on repetitive traffic, with zero compiled
    shapes added after warmup."""

    def _streams(self, engine, reqs):
        from kubeshare_tpu.serving import Request

        for req in reqs:
            engine.submit(Request(**req))
        return {rid: r.tokens for rid, r in engine.run().items()}

    def _workload(self, rng, sampled=False):
        base = rng.integers(0, 64, 6)
        reqs = [
            # repetitive prompts: the traffic speculation exists for
            dict(rid="rep0", prompt=np.tile(base, 4)[:22],
                 max_new_tokens=10),
            dict(rid="rep1", prompt=np.tile(rng.integers(0, 64, 4),
                                            5)[:17], max_new_tokens=8),
            # incompressible control lane rides verify at width 1
            dict(rid="rand", prompt=rng.integers(0, 64, 9),
                 max_new_tokens=6),
        ]
        if sampled:
            reqs.append(dict(rid="samp", prompt=np.tile(base, 3)[:15],
                             max_new_tokens=9, temperature=0.8,
                             rng=jax.random.PRNGKey(43)))
        return reqs

    def test_streams_bit_exact_spec_on_vs_off_across_configs(self):
        """Speculation on vs off, token for token, same workload —
        GQA+RoPE (with sampled lanes: the key schedule must be
        consumed identically through verify chunks), windowed
        attention, and MoE."""
        cases = {
            "gqa_rope": dict(n_kv_heads=2, positional="rope"),
            "windowed": dict(attention_window=6),
            "moe": dict(moe_every=2, moe_num_experts=4, moe_top_k=2),
        }
        accepted_total = 0
        for name, extra in cases.items():
            config = _small_config(**extra)
            params = transformer_init(jax.random.PRNGKey(0), config)
            rng = np.random.default_rng(52)
            sampled = name == "gqa_rope"
            workload = self._workload(rng, sampled=sampled)
            kwargs = dict(top_k=10, top_p=0.95) if sampled else {}
            on = _engine(params, config, speculative=True, draft_len=4,
                         **kwargs)
            off = _engine(params, config, **kwargs)
            got = self._streams(on, workload)
            want = self._streams(off, workload)
            assert got == want, name
            # speculation actually engaged (and the control arm's
            # sequential scheduler never verified)
            assert on.verify_steps > 0, name
            assert sum(on.spec_drafted.values()) > 0, name
            accepted_total += sum(on.spec_accepted.values())
            assert off.verify_steps == 0, name
        # whether a random-weight model's picks ever agree with the
        # lookup is per-config luck; across three configs some drafts
        # must land (acceptance QUALITY is locked in
        # test_fewer_dispatches_on_repetitive_trace)
        assert accepted_total > 0

    def test_streams_bit_exact_with_mixed_off(self):
        """Speculation composes with the either/or scheduler too —
        verify chunks replace decode spans identically when prefill
        never fuses."""
        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(53)
        workload = self._workload(rng)
        on = _engine(params, config, speculative=True, draft_len=4,
                     mixed=False)
        off = _engine(params, config, mixed=False)
        got = self._streams(on, workload)
        want = self._streams(off, workload)
        assert got == want
        assert on.verify_steps > 0
        assert on.mixed_verify_steps == 0 == on.mixed_steps

    def test_dense_and_paged_speculative_parity(self):
        """Satellite: the dense two-model speculative path
        (models/decoding.py) self-drafting and the engine's
        prompt-lookup path share one acceptance rule
        (speculative_acceptance) — self-drafted dense, engine
        speculative, and the plain greedy oracle all emit the SAME
        stream."""
        from kubeshare_tpu.models.decoding import (greedy_decode,
                                                   speculative_greedy_decode)
        from kubeshare_tpu.serving import Request

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(54)
        prompt = np.tile(rng.integers(0, 64, 5), 4)[:18]
        oracle = np.asarray(greedy_decode(
            params, config, jnp.asarray(prompt)[None], 8))[0]
        dense = np.asarray(speculative_greedy_decode(
            params, config, params, config,
            jnp.asarray(prompt)[None], 8, draft_len=4))[0]
        engine = _engine(params, config, speculative=True, draft_len=4)
        engine.submit(Request("r0", prompt, 8))
        paged = engine.run()["r0"].tokens
        assert list(oracle) == list(dense) == paged

    def test_zero_recompiles_after_warmup(self):
        """Acceptance criterion: warmup covers every verify width the
        adaptive controller can reach (and the fused mixed-verify
        cross product) — a speculative workload with admissions,
        prefill fusion, drafting lanes and width adaptation compiles
        NOTHING new."""
        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _engine(params, config, speculative=True, draft_len=4)
        engine.warmup()
        baseline = engine.compile_counts()
        assert baseline["verify"] > 0
        assert baseline["mixed_verify"] > 0
        rng = np.random.default_rng(55)
        self._streams(engine, self._workload(rng, sampled=True))
        assert engine.verify_steps > 0
        assert engine.compile_counts() == baseline

    def test_fewer_dispatches_on_repetitive_trace(self):
        """The shape of the saving, as a count: on a model that repeats
        (`_cyclic_params`) the verify path spends fewer target
        dispatches per emitted token than sequential decoding at
        decode_span=1 — same stream."""
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = _cyclic_params(config)
        rng = np.random.default_rng(56)
        prompt = np.tile(rng.integers(0, 64, 4), 8)[:30]
        counts = {}
        streams = {}
        for spec in (True, False):
            engine = _engine(params, config, speculative=spec,
                             draft_len=8, decode_span=1)
            engine.submit(Request("r0", prompt, 14))
            streams[spec] = engine.run()["r0"].tokens
            counts[spec] = engine.decode_steps + engine.verify_steps
        assert streams[True] == streams[False]
        assert counts[True] < counts[False]

    def test_preemption_resume_bit_exact_with_speculation(self):
        """Acceptance criterion: cache-backed preemption under a
        speculative engine — the victim's drafter is rebuilt from
        prompt + generated on resume and every stream still matches
        the greedy oracle.  The drafter-window invariant
        (history == prompt + generated, the resume-rebuild contract)
        is asserted on every decode lane at every step."""
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
            max_request_len=32, prefill_chunk=8, speculative=True,
            draft_len=4), tenants=registry)
        rng = np.random.default_rng(57)
        # repetitive victims: the resumed lane must KEEP drafting from
        # its rebuilt window (pre-preemption emissions included)
        p0 = np.tile(rng.integers(0, 64, 5), 1)
        p1 = rng.integers(0, 64, 5)
        pg = rng.integers(0, 64, 10)

        def check_drafter_invariant():
            for s in engine._slots:
                if s.state == "decode" and s.drafter is not None:
                    assert s.drafter.history == \
                        list(s.prompt) + list(s.generated), s.rid

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
            check_drafter_invariant()
        engine.submit(Request("gold", pg, 4, tenant="gold"))
        results = {}
        while engine.step():
            check_drafter_invariant()
            for rid, res in list(engine._results.items()):
                if res.finished_at is not None:
                    results[rid] = res
        assert engine.preemptions.get("batch", 0) >= 1
        for rid, prompt, new in (("v0", p0, 19), ("v1", p1, 19),
                                 ("gold", pg, 4)):
            ref = np.asarray(greedy_decode(
                params, config, jnp.asarray(prompt, jnp.int32)[None],
                new))[0]
            assert results[rid].tokens == list(ref), rid
        assert engine.allocator.blocks_in_use == 0

    def test_spec_metrics_on_plane(self):
        """Satellite: drafted/accepted counters and the per-tenant
        acceptance-rate histogram ride the promtext scrape surface and
        reconcile with the engine's own counters."""
        from kubeshare_tpu.serving import Request
        from kubeshare_tpu.utils.promtext import encode_families, parse_text

        config = _small_config()
        params = _cyclic_params(config)
        engine = _engine(params, config, speculative=True, draft_len=4)
        rng = np.random.default_rng(58)
        prompt = np.tile(rng.integers(0, 64, 4), 6)[:22]
        engine.submit(Request("r0", prompt, 10))
        engine.run()
        assert engine.verify_steps > 0
        samples = {(s.name, tuple(sorted(s.labels.items()))): s.value
                   for s in parse_text(
                       encode_families(engine.collect_metrics()))}
        drafted = engine.spec_drafted.get("default", 0)
        accepted = engine.spec_accepted.get("default", 0)
        assert drafted > 0 and 0 < accepted <= drafted
        assert samples[("kubeshare_serving_spec_tokens_total",
                        (("kind", "drafted"),
                         ("tenant", "default")))] == drafted
        assert samples[("kubeshare_serving_spec_tokens_total",
                        (("kind", "accepted"),
                         ("tenant", "default")))] == accepted
        # one histogram observation per drafting verify round
        rounds = samples[("kubeshare_serving_spec_acceptance_ratio_count",
                          (("tenant", "default"),))]
        assert 0 < rounds <= engine.verify_steps
        # the +Inf bucket is cumulative: every round lands in it
        assert samples[("kubeshare_serving_spec_acceptance_ratio_bucket",
                        (("le", "+Inf"),
                         ("tenant", "default")))] == rounds
        kinds = {k[1][0][1]: v for k, v in samples.items()
                 if k[0] == "kubeshare_serving_dispatches_total"}
        assert kinds["verify_span"] + kinds["mixed_verify"] == \
            engine.verify_steps
