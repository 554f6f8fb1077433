"""Serving subsystem tests: the radix prefix cache.

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


class TestPrefixCache:
    """The tentpole's contract: prefix-cached serving emits EXACTLY the
    streams the cache-disabled engine (and the dense reference) emits —
    across GQA/windowed/MoE/sampled configs, with shared blocks
    refcounted, mid-block divergence copied-on-write, and eviction
    deferred until a reservation would otherwise fail."""

    def _run_sequentially(self, engine, reqs):
        """Submit+drain one at a time so earlier requests' blocks are
        in the cache before later lookups (live traffic's steady state)."""
        from kubeshare_tpu.serving import Request

        out = {}
        for req in reqs:
            engine.submit(Request(**req))
            out.update({rid: r.tokens for rid, r in engine.run().items()
                        if r.done})
            engine.pop_finished()
        return out

    def test_streams_bit_exact_with_cache_disabled_across_configs(self):
        """Cache on vs cache off, token for token — full-block reuse,
        mid-block CoW divergence, and a fully cached prompt, under every
        attention variant the dense oracle covers."""
        cases = {
            "gqa_rope": dict(n_kv_heads=2, positional="rope"),
            "windowed": dict(attention_window=6),
            "moe": dict(moe_every=2, moe_num_experts=4, moe_top_k=2),
        }
        rng = np.random.default_rng(11)
        base = rng.integers(0, 64, 21)  # 5 full blocks (bs 4) + 1 token
        diverge = base.copy()
        diverge[18] = (diverge[18] + 1) % 64  # mid-block divergence
        reqs = [
            dict(rid="cold", prompt=base, max_new_tokens=6),
            dict(rid="exact", prompt=base.copy(), max_new_tokens=4),
            dict(rid="cow", prompt=diverge, max_new_tokens=6),
            dict(rid="short", prompt=base[:10].copy(), max_new_tokens=3),
        ]
        for name, extra in cases.items():
            config = _small_config(**extra)
            params = transformer_init(jax.random.PRNGKey(0), config)
            cached = _engine(params, config)
            plain = _engine(params, config, prefix_cache=False)
            got = self._run_sequentially(cached, reqs)
            want = self._run_sequentially(plain, reqs)
            assert got == want, name
            assert cached.prefix_hit_tokens > 0, name
            assert cached.cow_copies >= 1, name  # the divergence copied
            assert plain.prefix_hit_tokens == 0

    def test_sampled_streams_bit_exact_with_prefix_hits(self):
        """The key schedule must survive a cache hit: a sampled request
        admitted onto a matched prefix reproduces its solo stream."""
        from kubeshare_tpu.models.decoding import sample_decode

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(3), (14,), 0, 64), np.int32)
        rng = jax.random.PRNGKey(9)
        reqs = [
            dict(rid="warm", prompt=prompt, max_new_tokens=3),
            dict(rid="samp", prompt=prompt.copy(), max_new_tokens=5,
                 temperature=0.8, rng=rng),
        ]
        engine = _engine(params, config, top_k=10, top_p=0.95)
        got = self._run_sequentially(engine, reqs)
        assert engine.prefix_hit_tokens == 13  # prompt-1 cap
        ref = np.asarray(sample_decode(
            params, config, jnp.asarray(prompt)[None], rng, 5,
            temperature=0.8, top_k=10, top_p=0.95))[0]
        assert got["samp"] == list(ref)

    def test_cow_divergence_does_not_corrupt_cached_prefix(self):
        """The corruption a CoW exists to prevent: after a diverging
        request appends into (a copy of) the shared tail block, the
        ORIGINAL cached stream must still replay exactly."""
        from kubeshare_tpu.models.decoding import greedy_decode

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(4)
        a = rng.integers(0, 64, 10)  # bs 4: 2 full blocks + 2-token tail
        b = a.copy()
        b[9] = (b[9] + 7) % 64  # diverges at the tail block's 2nd row
        engine = _engine(params, config)
        got = self._run_sequentially(engine, [
            dict(rid="a1", prompt=a, max_new_tokens=6),
            dict(rid="b", prompt=b, max_new_tokens=6),
            dict(rid="a2", prompt=a.copy(), max_new_tokens=6),
        ])
        assert engine.cow_copies >= 1
        for rid, prompt in (("a1", a), ("b", b), ("a2", a)):
            ref = np.asarray(greedy_decode(
                params, config, jnp.asarray(prompt, jnp.int32)[None], 6))[0]
            assert got[rid] == list(ref), rid
        assert got["a1"] == got["a2"]

    def test_eviction_only_when_reserve_would_fail(self):
        """Cached blocks survive admissions the free list can fund and
        are drained (LRU) exactly when a reservation would otherwise
        raise BlockExhausted."""
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        # 12 allocatable blocks x 4 rows = 48 rows
        engine = _engine(params, config, num_slots=1, num_blocks=13,
                         max_request_len=32)
        rng = np.random.default_rng(7)
        engine.submit(Request("r0", rng.integers(0, 64, 13), 3))  # 4 blocks
        engine.run()
        cached_after_r0 = engine.allocator.cached_idle_blocks
        assert cached_after_r0 == 4  # 3 full + partial tail, all idle now
        # 8 free blocks fund this without touching the cache
        engine.submit(Request("r1", rng.integers(0, 64, 17), 3))  # 5 blocks
        engine.run()
        assert engine.allocator.evicted_blocks == 0
        assert engine.allocator.cached_idle_blocks > cached_after_r0
        # free list now 3; this needs 8 -> the LRU pool must drain
        engine.submit(Request("r2", rng.integers(0, 64, 29), 3))
        engine.run()
        assert engine.allocator.evicted_blocks > 0
        assert engine.allocator.blocks_in_use == 0
        assert (engine.allocator.free_blocks
                + engine.allocator.cached_idle_blocks
                == engine.allocator.num_blocks - 1)

    def test_exhaustion_with_inflight_decodes_keeps_slots_intact(self):
        """Regression (satellite): BlockExhausted at admission with
        decodes in flight must not disturb running slots; the queued
        request stays pending and admits once retirement frees blocks —
        with the cache, after LRU eviction — and still emits its solo
        reference stream."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(9)
        # 12 allocatable blocks; r0/r1 take 6 each -> r2 (7) must wait
        engine = _engine(params, config, num_slots=3, num_blocks=13,
                         max_request_len=32)
        p0 = rng.integers(0, 64, 17)  # 17+6=23 rows -> 6 blocks
        p1 = rng.integers(0, 64, 18)  # 18+6=24 rows -> 6 blocks
        p2 = rng.integers(0, 64, 21)  # 21+6=27 rows -> 7 blocks
        engine.submit(Request("r0", p0, 6))
        engine.submit(Request("r1", p1, 6))
        engine.submit(Request("r2", p2, 6))
        # drive until r0 and r1 are BOTH decoding with r2 still queued
        while (engine.result("r0").first_token_at is None
               or engine.result("r1").first_token_at is None):
            assert engine.step()
        assert engine.result("r0").admitted_at is not None
        assert engine.result("r1").admitted_at is not None
        assert engine.result("r2").admitted_at is None  # pending, not lost
        assert engine.allocator.free_blocks == 0
        out = engine.run()  # a retirement funds r2 (eviction included)
        assert engine.allocator.evicted_blocks > 0
        for rid, prompt in (("r0", p0), ("r1", p1), ("r2", p2)):
            ref = np.asarray(greedy_decode(
                params, config, jnp.asarray(prompt, jnp.int32)[None], 6))[0]
            assert out[rid].tokens == list(ref), rid

    def test_zero_recompiles_with_cache_hits_and_cow(self):
        """Acceptance criterion: warmup covers everything the cache can
        dispatch — matched-prefix prefills at arbitrary start positions,
        the CoW copy, eviction-funded admissions — so a shared-prefix
        workload adds ZERO compiled shapes."""
        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _engine(params, config)
        engine.warmup()
        baseline = engine.compile_counts()
        assert baseline["copy"] == 1  # the cache's single extra shape
        rng = np.random.default_rng(6)
        shared = rng.integers(0, 64, 19)
        reqs = [dict(rid="cold", prompt=shared, max_new_tokens=4)]
        for i in range(6):  # full hits, mid-block CoW, ragged suffixes
            prompt = np.concatenate(
                [shared[: 11 + i], rng.integers(0, 64, 2 + i)])
            reqs.append(dict(rid=f"r{i}", prompt=prompt,
                             max_new_tokens=3 + i % 3))
        self._run_sequentially(engine, reqs)
        assert engine.prefix_hit_requests > 0 and engine.cow_copies > 0
        assert engine.compile_counts() == baseline

    def test_metrics_endpoint_scrapes_serving_plane(self):
        """Satellite: the engine exports its runtime counters through
        the same promtext textfile server the token daemons use — a
        stock Prometheus scrape, parsed back with the house parser."""
        import urllib.request

        from kubeshare_tpu.utils.promtext import parse_text

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _engine(params, config)
        rng = np.random.default_rng(2)
        shared = rng.integers(0, 64, 12)
        self._run_sequentially(engine, [
            dict(rid="m0", prompt=shared, max_new_tokens=4),
            dict(rid="m1", prompt=shared.copy(), max_new_tokens=3),
        ])
        server = engine.serve_metrics(port=0)
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/kubeshare-serving",
                timeout=5).read().decode()
        finally:
            server.stop()
        samples = {(s.name, tuple(sorted(s.labels.items()))): s.value
                   for s in parse_text(body)}
        req = "kubeshare_serving_requests_total"
        assert samples[(req, (("stage", "admitted"),))] == 2
        assert samples[(req, (("stage", "finished"),))] == 2
        assert samples[("kubeshare_serving_prefix_hit_tokens_total",
                        ())] == 11  # m1 matched prompt-1 tokens
        blocks = {k[1][0][1]: v for k, v in samples.items()
                  if k[0] == "kubeshare_serving_kv_blocks"}
        assert blocks["in_use"] == 0
        assert (blocks["free"] + blocks["cached"]
                == engine.allocator.num_blocks - 1)
        # histogram: every finished request's TTFT observed
        assert samples[("kubeshare_serving_ttft_seconds_count", ())] == 2
        assert samples[("kubeshare_serving_ttft_seconds_bucket",
                        (("le", "+Inf"),))] == 2
