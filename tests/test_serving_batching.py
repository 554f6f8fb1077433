"""Serving subsystem tests: continuous batching, the prefill plan and
ragged prefill buckets.

The contract is the one ``tests/test_serving.py`` states: the paged pool +
continuous-batching engine emit EXACTLY the token stream the dense-cache
reference paths emit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models.transformer import TransformerConfig, transformer_init

from serving_helpers import _engine, _small_config

pytestmark = pytest.mark.serving


class TestContinuousBatching:
    def test_mixed_lengths_match_solo_references(self):
        """The killer property: 10 mixed-length requests squeezed
        through 3 slots — admitted mid-flight, recycling retired slots'
        blocks — each emit exactly their SOLO dense-path stream."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(3)
        # 7 requests over 3 slots; lengths chosen to hit full-chunk,
        # ragged-tail, and short-pad prefill plans (repeated (L, new)
        # pairs keep the dense-reference compile count down — tier-1
        # time is compile-dominated at this model size)
        shapes = [(1, 3), (5, 8), (13, 4), (21, 11), (5, 8), (13, 4),
                  (29, 2)]
        reqs = [(f"r{i}", rng.integers(0, 64, length), new)
                for i, (length, new) in enumerate(shapes)]
        engine = _engine(params, config)
        for rid, prompt, new in reqs:
            engine.submit(Request(rid, prompt, new))
        out = engine.run()
        for rid, prompt, new in reqs:
            ref = np.asarray(greedy_decode(
                params, config, jnp.asarray(prompt, jnp.int32)[None], new))[0]
            assert out[rid].tokens == list(ref), rid
        # every retired request's blocks went home: refcounts all dropped,
        # and each block is either free or parked in the prefix cache's
        # idle pool (evictable on demand — still admission-fundable)
        assert engine.allocator.blocks_in_use == 0
        assert (engine.allocator.free_blocks
                + engine.allocator.cached_idle_blocks
                == engine.allocator.num_blocks - 1)
        assert engine.allocator.available_blocks == engine.allocator.num_blocks - 1
        # a live-loop server evicts completed results instead of letting
        # the result map grow with every request ever served
        popped = engine.pop_finished()
        assert sorted(popped) == sorted(rid for rid, _, _ in reqs)
        assert engine.pop_finished() == {}
        # and the pool was actually oversubscribed: peak in-use is under
        # what 10 requests would need simultaneously
        total_demand = sum(
            engine.allocator.blocks_for_tokens(len(p) + n)
            for _, p, n in reqs)
        assert 0 < engine.peak_blocks_in_use < total_demand

    def test_admission_waits_on_block_exhaustion(self):
        """A request the pool can't fund YET queues (no clamp, no drop)
        and admits after a retirement frees blocks; a request that can
        NEVER fit fails loudly at submit."""
        from kubeshare_tpu.serving import BlockExhausted, Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        # 6 allocatable blocks x 4 = 24 rows total
        engine = _engine(params, config, num_slots=2, num_blocks=7,
                         max_request_len=32)
        prompt = np.zeros(17, np.int32)  # 17 + 3 -> 5 blocks each
        engine.submit(Request("big0", prompt, 3))
        engine.submit(Request("big1", prompt, 3))
        engine.step()  # admits big0 (5 blocks); big1 (5 > 3 free) waits
        assert engine.result("big0").admitted_at is not None
        assert engine.result("big1").admitted_at is None
        out = engine.run()  # big0 retires -> big1 admits and completes
        assert len(out["big1"].tokens) == 3
        with pytest.raises(BlockExhausted, match="NEVER"):
            engine.submit(Request("huge", np.zeros(30, np.int32), 2))

    def test_submit_validation_is_loud(self):
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _engine(params, config)
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.submit(Request("a", np.zeros(4, np.int32), 0))
        with pytest.raises(ValueError, match="max_request_len"):
            engine.submit(Request("b", np.zeros(40, np.int32), 20))
        with pytest.raises(ValueError, match="rng"):
            engine.submit(Request("c", np.zeros(4, np.int32), 2,
                                  temperature=0.7))
        with pytest.raises(ValueError, match="non-empty"):
            engine.submit(Request("d", np.zeros(0, np.int32), 2))

    def test_short_pool_caps_pad_bucket(self):
        """A max_request_len below the prefill bucket must not reject a
        request that actually fits (review regression): prompt 17 +
        3 new = 20 rows in a 24-row bound with chunk 32 used to be
        refused over the uncapped 32-row pad bucket."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _engine(params, config, num_slots=2, num_blocks=15,
                         max_request_len=24, prefill_chunk=32)
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(5), (17,), 0, 64), np.int32)
        engine.warmup()
        baseline = engine.compile_counts()
        engine.submit(Request("r0", prompt, 3))
        out = engine.run()["r0"]
        ref = np.asarray(greedy_decode(
            params, config, jnp.asarray(prompt)[None], 3))[0]
        assert out.tokens == list(ref)
        # the capped (non-power-of-two) pad width was part of warmup
        assert engine.compile_counts() == baseline

    def test_eos_retires_early_and_frees_blocks(self):
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (9,), 0, 64), np.int32)
        ref = [int(t) for t in np.asarray(greedy_decode(
            params, config, jnp.asarray(prompt)[None], 8))[0]]
        eos = ref[2]  # the 3rd greedy token becomes "EOS"
        engine = _engine(params, config, eos_token=eos)
        engine.submit(Request("r0", prompt, 8))
        out = engine.run()["r0"]
        # stops AT the stream's first eos occurrence (which may precede
        # index 2 if the token repeats), mid-decode-span included
        assert out.tokens == ref[: ref.index(eos) + 1]
        assert len(out.tokens) < len(ref)
        assert engine.allocator.blocks_in_use == 0

    def test_zero_recompilation_after_warmup(self):
        """The acceptance criterion, asserted via jit cache stats: after
        warmup, a full mixed ragged workload adds ZERO compilations, and
        the prefill widths stay within the O(log chunk) bucket bound."""
        import math

        from kubeshare_tpu.serving import Request

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _engine(params, config)
        engine.warmup()
        baseline = engine.compile_counts()
        chunk = engine.engine_config.prefill_chunk
        # widths bucketed to powers of two, lane counts to {1, num_slots}
        assert baseline["prefill"] <= 2 * (int(math.log2(chunk)) + 1)
        assert baseline["decode"] == 1
        rng = np.random.default_rng(5)
        for i in range(8):  # every remainder class over two waves
            engine.submit(Request(
                f"r{i}", rng.integers(0, 64, 2 * chunk + 1 + i),
                int(rng.integers(1, 6))))
        engine.run()
        assert engine.compile_counts() == baseline

    def test_engine_charges_through_guard(self):
        """Fractional-chip integration: every prefill chunk / decode
        step / first-token pick acquires and charges the token guard."""
        from kubeshare_tpu.isolation.guard import ExecutionGuard
        from kubeshare_tpu.serving import EngineConfig, Request, ServingEngine

        class FakeClient:
            def __init__(self):
                self.acquired = 0
                self.released_ms = 0.0

            def acquire(self, estimate_ms):
                self.acquired += 1
                return 1e9  # one grant funds the whole run

            def release(self, used_ms):
                self.released_ms += used_ms

        client = FakeClient()
        guard = ExecutionGuard(client=client, from_env=False,
                               idle_release_ms=0)
        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = ServingEngine(
            params, config,
            EngineConfig(num_slots=2, block_size=4, num_blocks=17,
                         max_request_len=32, prefill_chunk=8),
            guard=guard)
        engine.submit(Request("r0", np.zeros(9, np.int32), 4))
        engine.run()
        assert client.acquired >= 1
        assert guard.total_gated_ms > 0.0
        # run() returned the held token at drain
        assert client.released_ms > 0.0


class TestPrefillPlan:
    """Satellite: plan_prefill_chunks edge cases — the exact prompt
    geometries a block-paged admission path must not fumble."""

    def test_one_token_prompt(self):
        from kubeshare_tpu.serving import plan_prefill_chunks

        plan, cover = plan_prefill_chunks(1, 8, 48)
        assert plan == [(0, 1, 0)] and cover == 1

    def test_prompt_shorter_than_one_block(self):
        from kubeshare_tpu.serving import plan_prefill_chunks

        # 3 tokens, chunk 8 -> one bucketed pad-forward chunk of width 4
        plan, cover = plan_prefill_chunks(3, 8, 48)
        assert plan == [(0, 4, 2)] and cover == 4

    def test_prompt_exact_chunk_multiple(self):
        from kubeshare_tpu.serving import plan_prefill_chunks

        plan, cover = plan_prefill_chunks(16, 8, 48)
        assert plan == [(0, 8, 7), (8, 8, 7)] and cover == 16

    def test_start_offset_plans_suffix_only(self):
        from kubeshare_tpu.serving import plan_prefill_chunks

        # matched 16 of 21: one bucketed tail sliding back to end at 20
        plan, cover = plan_prefill_chunks(21, 8, 48, start=16)
        assert plan == [(13, 8, 7)] and cover == 21
        # matched 16 of 17: a single width-1 chunk at the last token
        plan, cover = plan_prefill_chunks(17, 8, 48, start=16)
        assert plan == [(16, 1, 0)] and cover == 17
        with pytest.raises(ValueError, match="start"):
            plan_prefill_chunks(8, 8, 48, start=8)

    def test_edge_prompts_add_no_compiled_shapes(self):
        """Engine-level lock: 1-token, sub-block, and exact-multiple
        prompts all ride warmup's bucketed widths — zero new compiles
        across all three."""
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _engine(params, config)  # block_size 4, chunk 8
        engine.warmup()
        baseline = engine.compile_counts()
        rng = np.random.default_rng(8)
        for i, length in enumerate((1, 3, 16)):
            engine.submit(Request(f"e{i}", rng.integers(0, 64, length), 2))
        out = engine.run()
        assert all(len(r.tokens) == 2 for r in out.values())
        assert engine.compile_counts() == baseline


class TestRaggedPrefill:
    """Satellite: prefill_chunked accepts non-tiling prompts via
    power-of-two bucketed final chunks."""

    def test_matches_bulk_across_remainders(self):
        from kubeshare_tpu.models.decoding import prefill, prefill_chunked

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        # short-pad, pow2, ragged-with-full-chunks, exact-tile, long-ragged
        for length in (3, 8, 11, 16, 21):
            prompt = jax.random.randint(
                jax.random.PRNGKey(length), (2, length), 0, 64)
            cache_b, logits_b = prefill(params, config, prompt)
            cache_c, logits_c = prefill_chunked(params, config, prompt, 8)
            np.testing.assert_allclose(
                np.asarray(logits_c), np.asarray(logits_b),
                rtol=2e-4, atol=2e-4, err_msg=f"L={length}")
            np.testing.assert_allclose(
                np.asarray(cache_c["k"]), np.asarray(cache_b["k"]),
                rtol=2e-4, atol=2e-4, err_msg=f"L={length}")
            np.testing.assert_allclose(
                np.asarray(cache_c["v"]), np.asarray(cache_b["v"]),
                rtol=2e-4, atol=2e-4, err_msg=f"L={length}")
            assert int(cache_c["length"]) == length

    def test_compile_count_bounded_by_buckets(self):
        """Compile-count regression: across EVERY remainder the chunk
        widths hitting the compiler stay within {chunk} + powers of two
        — O(log chunk) shapes, not one per remainder."""
        import math

        from kubeshare_tpu.models import decoding

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference")
        params = transformer_init(jax.random.PRNGKey(0), config)
        chunk = 8
        widths = set()
        real = decoding._decode_chunk

        def recording(params, config, cache, tokens, *args, **kwargs):
            widths.add(int(tokens.shape[1]))
            return real(params, config, cache, tokens, *args, **kwargs)

        try:
            decoding._decode_chunk = recording
            for length in range(1, 2 * chunk + 1):
                prompt = jnp.zeros((1, length), jnp.int32)
                decoding.prefill_chunked(params, config, prompt, chunk)
        finally:
            decoding._decode_chunk = real
        allowed = {chunk} | {2 ** i for i in range(int(math.log2(chunk)) + 1)}
        assert widths <= allowed, widths
        assert len(widths) <= int(math.log2(chunk)) + 1

    def test_bucket_capped_at_max_seq_len(self):
        """A non-power-of-two max_seq_len below the bucket must not make
        the pad-forward chunk overrun the cache (review regression):
        prompt 17 in a 20-row cache with chunk 32 bucketed to 32 used to
        crash in XLA."""
        from kubeshare_tpu.models.decoding import prefill, prefill_chunked
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
            max_seq_len=20, dtype=jnp.float32, attention="reference")
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 17), 0, 64)
        cache_b, logits_b = prefill(params, config, prompt)
        cache_c, logits_c = prefill_chunked(params, config, prompt, 32)
        np.testing.assert_allclose(
            np.asarray(logits_c), np.asarray(logits_b),
            rtol=2e-4, atol=2e-4)
        assert int(cache_c["length"]) == 17

    def test_bucket_width(self):
        from kubeshare_tpu.models.decoding import bucket_width

        assert [bucket_width(r, 8) for r in (1, 2, 3, 4, 5, 7, 8)] == [
            1, 2, 4, 4, 8, 8, 8]
        with pytest.raises(ValueError):
            bucket_width(0, 8)
        with pytest.raises(ValueError):
            bucket_width(9, 8)
