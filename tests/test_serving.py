"""Serving subsystem tests: paged KV cache, continuous batching, ragged
prefill buckets.

The contract under test is the strongest one a serving stack can make:
the paged pool + continuous-batching engine must emit EXACTLY the token
stream the dense-cache reference paths emit — per request, regardless of
what else is co-batched in the pool, which slot the request landed in,
or whose blocks it recycled.  Plus the allocator's loud-failure
discipline and the zero-recompile property the TPU serving story depends
on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models.transformer import TransformerConfig, transformer_init

pytestmark = pytest.mark.serving


def _small_config(**extra):
    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32, attention="reference", **extra)


def _cyclic_params(config):
    """Weights of a model whose continuation the test controls, for the
    tests that need drafts ACCEPTED: `transformer_init`'s, with every
    layer's `wo` and `w_out` (and `pos_embed`) zeroed, so the residual
    stream is the last token's embedding, and an `lm_head` that reads
    the embedding of `t` back as `t ^ 1`.  Greedy decoding alternates between two ids from its first
    token on, whatever the prompt, so the n-gram drafter proposes what
    the model emits from the fourth token; the head is scaled up so that
    sampled lanes mostly follow the cycle too.  The random-weight model
    of the other tests repeats only by luck."""
    params = transformer_init(jax.random.PRNGKey(0), config)
    embed = params["embed"]
    successor = jnp.arange(embed.shape[0]) ^ 1
    layers = []
    for layer in params["layers"]:
        layer = dict(layer)
        layer["attn"] = dict(layer["attn"],
                             wo=jnp.zeros_like(layer["attn"]["wo"]))
        layer["mlp"] = dict(layer["mlp"],
                            w_out=jnp.zeros_like(layer["mlp"]["w_out"]))
        layers.append(layer)
    out = dict(params, layers=layers, lm_head=8.0 * embed[successor].T)
    if "pos_embed" in params:
        out["pos_embed"] = jnp.zeros_like(params["pos_embed"])
    normed = embed * jax.lax.rsqrt(jnp.mean(embed ** 2, -1, keepdims=True))
    logits = normed @ out["lm_head"]
    assert (jnp.argmax(logits, -1) == successor).all()
    return out


def _engine(params, config, **overrides):
    from kubeshare_tpu.serving import EngineConfig, ServingEngine

    kwargs = dict(num_slots=3, block_size=4, num_blocks=41,
                  max_request_len=48, prefill_chunk=8)
    kwargs.update(overrides)
    return ServingEngine(params, config, EngineConfig(**kwargs))


class TestBlockAllocator:
    def test_exhaustion_is_loud_and_all_or_nothing(self):
        from kubeshare_tpu.serving import BlockAllocator, BlockExhausted

        alloc = BlockAllocator(num_blocks=5, block_size=4)  # 4 allocatable
        got = alloc.reserve(3, "a")
        assert len(got) == 3 and 0 not in got
        with pytest.raises(BlockExhausted, match="needs 2 blocks"):
            alloc.reserve(2, "b")
        # the failed reservation granted NOTHING
        assert alloc.free_blocks == 1
        assert alloc.blocks_in_use == 3

    def test_double_free_raises(self):
        from kubeshare_tpu.serving import BlockAllocator

        alloc = BlockAllocator(num_blocks=5, block_size=4)
        blocks = alloc.reserve(2, "a")
        alloc.reclaim(blocks)
        with pytest.raises(ValueError, match="double free"):
            alloc.reclaim(blocks)
        with pytest.raises(ValueError, match="not allocated"):
            alloc.reclaim([0])  # the scratch block is never allocated

    def test_reclaimed_blocks_are_reused_first(self):
        from kubeshare_tpu.serving import BlockAllocator

        alloc = BlockAllocator(num_blocks=9, block_size=4)
        first = alloc.reserve(3, "a")
        alloc.reclaim(first)
        again = alloc.reserve(3, "b")
        # LIFO free list: the retired request's blocks come back first
        assert set(again) == set(first)

    def test_blocks_for_tokens(self):
        from kubeshare_tpu.serving import BlockAllocator

        alloc = BlockAllocator(num_blocks=9, block_size=4)
        assert [alloc.blocks_for_tokens(n) for n in (1, 4, 5, 8, 9)] == [
            1, 1, 2, 2, 3]


class TestPagedEquivalence:
    """Greedy and sampled streams from the paged pool must match the
    dense cache exactly — the bit-exactness the ISSUE's read path
    promises, locked at the emitted-token level."""

    def test_greedy_matches_dense_across_configs(self):
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request

        cases = {
            "mha": dict(),
            "gqa_rope": dict(n_kv_heads=2, positional="rope"),
            "windowed": dict(attention_window=6),
            "moe": dict(moe_every=2, moe_num_experts=4, moe_top_k=2),
        }
        for name, extra in cases.items():
            config = _small_config(**extra)
            params = transformer_init(jax.random.PRNGKey(0), config)
            prompt = np.asarray(jax.random.randint(
                jax.random.PRNGKey(1), (13,), 0, 64), np.int32)
            dense = np.asarray(greedy_decode(
                params, config, jnp.asarray(prompt)[None], 8))[0]
            engine = _engine(params, config)
            engine.submit(Request("r0", prompt, 8))
            out = engine.run()["r0"]
            assert out.tokens == list(dense), name

    def test_sampled_matches_dense(self):
        """Same rng => the engine reproduces sample_decode_with_cache's
        stream exactly (temperature + top-k + top-p filtered)."""
        from kubeshare_tpu.models.decoding import sample_decode
        from kubeshare_tpu.serving import Request

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (10,), 0, 64), np.int32)
        rng = jax.random.PRNGKey(7)
        dense = np.asarray(sample_decode(
            params, config, jnp.asarray(prompt)[None], rng, 6,
            temperature=0.8, top_k=10, top_p=0.95))[0]
        engine = _engine(params, config, top_k=10, top_p=0.95)
        engine.submit(Request("r0", prompt, 6, temperature=0.8, rng=rng))
        out = engine.run()["r0"]
        assert out.tokens == list(dense)

    def test_paged_pool_rows_match_dense_cache(self):
        """Below the token level: the slot's gathered K/V rows equal the
        dense cache's rows after the same prefill."""
        from kubeshare_tpu.models.decoding import prefill
        from kubeshare_tpu.serving import Request, paged_gather_kv

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(2), (11,), 0, 64), np.int32)
        dense_cache, _ = prefill(params, config, jnp.asarray(prompt)[None])
        engine = _engine(params, config)
        engine.submit(Request("r0", prompt, 1))
        engine.run()
        # request retired; its prompt blocks are now in the prefix
        # cache — look them up by CONTENT and rebuild the virtual view
        matched, blocks = engine.prefix_index.match(prompt)
        assert matched == 11 and len(blocks) == 3  # 2 full + partial tail
        table = np.zeros(engine._table_width, np.int32)
        table[: len(blocks)] = blocks
        k_view, _ = paged_gather_kv(engine.pool.k, engine.pool.v,
                                    jnp.asarray(table))
        np.testing.assert_allclose(
            np.asarray(k_view[:, :, :11]),
            np.asarray(dense_cache["k"][:, 0, :, :11]),
            rtol=1e-6, atol=1e-6)


def _all_eqns(jaxpr):
    """Every equation of ``jaxpr``, sub-jaxprs (jit, scan, shard_map)
    included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _all_eqns(sub)


def _pool_step_case(name):
    """(fn(params, pool_k, pool_v), params, pool shape, pool shape on one
    device) for one of the four layer loops that write the pool, at a tiny
    GQA + rope config with every lane live."""
    from kubeshare_tpu.serving.paged import (
        paged_decode_step, paged_prefill_step, paged_verify_span)

    config = _small_config(n_kv_heads=2, positional="rope")
    params = transformer_init(jax.random.PRNGKey(0), config)
    lanes, width, chunk, bs, blocks = 3, 5, 4, 4, 17
    shape = (config.n_layers, blocks, config.kv_heads, bs, config.head_dim)
    tables = jnp.arange(1, 1 + lanes * width, dtype=jnp.int32).reshape(
        lanes, width)
    lengths = jnp.asarray([3, 6, 9], jnp.int32)
    active = jnp.ones((lanes,), bool)
    chunk_tokens = jnp.ones((lanes, chunk), jnp.int32)
    last_rows = jnp.zeros((lanes,), jnp.int32)

    if name == "paged_prefill_step":
        return (lambda w, pk, pv: paged_prefill_step(
            w, config, pk, pv, tables, lengths, active, chunk_tokens,
            last_rows), params, shape, shape)
    if name == "paged_decode_step":
        return (lambda w, pk, pv: paged_decode_step(
            w, config, pk, pv, tables, lengths, active,
            jnp.ones((lanes,), jnp.int32)), params, shape, shape)
    if name == "paged_verify_span":
        def pick(logits, temps, keys):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        return (lambda w, pk, pv: paged_verify_span(
            w, config, pick, pk, pv, tables, lengths, active, chunk_tokens,
            jnp.full((lanes,), chunk, jnp.int32),
            jnp.zeros((lanes,), jnp.float32),
            jnp.zeros((lanes, chunk, 2), jnp.uint32)), params, shape, shape)
    # sharded._chunk_stack, through the shard_map twin of the prefill
    # step: each of two devices holds one of the two KV heads
    from kubeshare_tpu.parallel.mesh import MeshSpec
    from kubeshare_tpu.serving.sharded import ShardedServingContext

    assert name == "sharded_prefill"
    ctx = ShardedServingContext(config, MeshSpec(dp=1, tp=2, sp=1), params)
    assert ctx.decision.attn_sharded
    return (lambda w, pk, pv: ctx.prefill(
        w, pk, pv, tables, lengths, active, chunk_tokens, last_rows),
        ctx.place_params(params), shape, shape[:2] + (1,) + shape[3:])


class TestPoolWrittenInPlace:
    """The K/V rows of a step are scattered into the pool buffer itself:
    no step builds a second pool (a restack of per-layer slabs) and none
    writes a layer's slab apart from it.  With the dense-equivalence tests
    above this pins "same rows, no restack"; what the TPU compiler makes
    of it is tests/test_chip_compile.py's to say."""

    @pytest.mark.parametrize("name", [
        "paged_prefill_step", "paged_decode_step", "paged_verify_span",
        "sharded_prefill"])
    def test_only_row_scatters_produce_a_pool(self, name):
        fn, params, shape, local = _pool_step_case(name)
        pool = jnp.zeros(shape, jnp.float32)
        eqns = list(_all_eqns(jax.make_jaxpr(fn)(params, pool, pool).jaxpr))

        def producers(shape):
            return [e.primitive.name for e in eqns
                    if any(getattr(v.aval, "shape", None) == shape
                           for v in e.outvars)]

        # K and V, once a layer, and nothing else makes a pool
        assert producers(local) == ["scatter"] * (2 * shape[0])
        # a layer's slab [B, h_kv, bs, d] is only ever read (the view's
        # window into the pool), never written and restacked
        assert "scatter" not in producers(local[1:])


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
