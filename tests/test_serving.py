"""Serving subsystem tests: the block allocator, the paged KV cache against
the dense cache, the pool written in place.

The contract under test is the strongest one a serving stack can make:
the paged pool + continuous-batching engine must emit EXACTLY the token
stream the dense-cache reference paths emit — per request, regardless of
what else is co-batched in the pool, which slot the request landed in,
or whose blocks it recycled.  Plus the allocator's loud-failure
discipline and the zero-recompile property the TPU serving story depends
on.

The suite is a file a subsystem (``tests/test_serving_*.py``: batching,
prefix cache, QoS, mixed batching, the KV tiers, speculative decoding, the
device loops, disaggregated pools) because a tier-1 worker holds a file for
its whole length (``--dist loadfile``): a class that outgrows its file goes
to a new one, and what the files share is ``tests/serving_helpers.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models.transformer import transformer_init

from serving_helpers import _all_eqns, _engine, _pool_step_case, _small_config

pytestmark = pytest.mark.serving


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
