"""Compute-path tests: the dense-cache decoders (greedy, sharded,
speculative) and sampling."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models import (
    TransformerConfig,
    transformer_apply,
    transformer_init,
)
from kubeshare_tpu.models.transformer import transformer_sharding_rules
from kubeshare_tpu.parallel import MeshSpec, make_mesh
from kubeshare_tpu.parallel.mesh import shard_params


class TestDecoding:
    def _setup(self):
        from kubeshare_tpu.models.transformer import TransformerConfig, transformer_init

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=32, dtype=jnp.float32, attention="reference",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        return config, params

    def test_incremental_matches_full_forward(self):
        # the incremental path explicitly: bulk prefill IS the dense
        # forward, so comparing it to dense would be a tautology
        from kubeshare_tpu.models.decoding import (
            prefill_incremental as prefill)

        config, params = self._setup()
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
        # cached incremental prefill must equal the dense forward's last step
        dense = transformer_apply(params, prompt, config)
        _, last_logits = prefill(params, config, prompt)
        np.testing.assert_allclose(
            np.asarray(dense[:, -1]), np.asarray(last_logits),
            rtol=2e-4, atol=2e-4,
        )

    def test_gqa_incremental_matches_full_forward(self):
        """GQA decode: the grouped cached-attention path (KV cache holds
        n_kv_heads, query heads grouped over it with no materialized
        repetition) must equal the dense GQA forward."""
        from kubeshare_tpu.models.decoding import (
            init_kv_cache, prefill_incremental as prefill)
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
            d_ff=64, max_seq_len=32, dtype=jnp.float32,
            attention="reference", positional="rope",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        # the cache — decode's dominant HBM cost — holds kv heads only
        assert init_kv_cache(config, 2)["k"].shape == (2, 2, 2, 32, 8)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
        dense = transformer_apply(params, prompt, config)
        _, last_logits = prefill(params, config, prompt)
        np.testing.assert_allclose(
            np.asarray(dense[:, -1]), np.asarray(last_logits),
            rtol=2e-4, atol=2e-4,
        )

    def test_bulk_prefill_matches_incremental(self):
        """The bulk prefill (one dense forward + bulk cache fill) must
        produce the same cache and logits as the token-at-a-time oracle —
        for MHA, GQA, and a MoE config (whose expert buffers prefill pins
        to the token count so routing stays position/batch-independent)."""
        from kubeshare_tpu.models.decoding import (
            greedy_decode, prefill, prefill_incremental)
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)

        cases = {
            "mha": dict(),
            "gqa_rope": dict(n_kv_heads=2, positional="rope"),
            "moe": dict(moe_every=2, moe_num_experts=4, moe_top_k=2),
            "windowed": dict(attention_window=6),
        }
        for name, extra in cases.items():
            config = TransformerConfig(
                vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_seq_len=32, dtype=jnp.float32, attention="reference",
                **extra)
            params = transformer_init(jax.random.PRNGKey(0), config)
            prompt = jax.random.randint(
                jax.random.PRNGKey(1), (2, 10), 0, 64)
            cache_b, logits_b = prefill(params, config, prompt)
            cache_i, logits_i = prefill_incremental(params, config, prompt)
            np.testing.assert_allclose(
                np.asarray(logits_b), np.asarray(logits_i),
                rtol=2e-4, atol=2e-4, err_msg=name)
            assert int(cache_b["length"]) == int(cache_i["length"]) == 10
            np.testing.assert_allclose(
                np.asarray(cache_b["k"]), np.asarray(cache_i["k"]),
                rtol=2e-4, atol=2e-4, err_msg=name)
            np.testing.assert_allclose(
                np.asarray(cache_b["v"]), np.asarray(cache_i["v"]),
                rtol=2e-4, atol=2e-4, err_msg=name)
            # and the next decode step computes identical logits from
            # either cache
            from kubeshare_tpu.models.decoding import _decode_one

            token = jnp.argmax(logits_b, axis=-1).astype(jnp.int32)
            step_b, _ = _decode_one(params, config, cache_b, token)
            step_i, _ = _decode_one(params, config, cache_i, token)
            np.testing.assert_allclose(
                np.asarray(step_b), np.asarray(step_i),
                rtol=2e-4, atol=2e-4, err_msg=name)
            out = greedy_decode(params, config, prompt, 4)
            assert out.shape == (2, 4)

    def test_chunked_prefill_matches_bulk(self):
        """Chunked prefill (O(chunk) activations per step) must produce
        the same cache and logits as the bulk dense pass — across
        MHA/GQA/MoE/windowed configs and chunk sizes incl. chunk=1 (which
        is exactly the incremental path) and chunk=prompt_len."""
        from kubeshare_tpu.models.decoding import prefill, prefill_chunked
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)

        cases = {
            "mha": dict(),
            "gqa_rope": dict(n_kv_heads=2, positional="rope"),
            "moe": dict(moe_every=2, moe_num_experts=4, moe_top_k=2),
            "windowed": dict(attention_window=6),
        }
        for name, extra in cases.items():
            config = TransformerConfig(
                vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_seq_len=32, dtype=jnp.float32, attention="reference",
                **extra)
            params = transformer_init(jax.random.PRNGKey(0), config)
            prompt = jax.random.randint(
                jax.random.PRNGKey(1), (2, 12), 0, 64)
            cache_b, logits_b = prefill(params, config, prompt)
            for chunk in (1, 4, 12):
                cache_c, logits_c = prefill_chunked(
                    params, config, prompt, chunk)
                np.testing.assert_allclose(
                    np.asarray(logits_c), np.asarray(logits_b),
                    rtol=2e-4, atol=2e-4, err_msg=f"{name} chunk={chunk}")
                np.testing.assert_allclose(
                    np.asarray(cache_c["k"]), np.asarray(cache_b["k"]),
                    rtol=2e-4, atol=2e-4, err_msg=f"{name} chunk={chunk}")
                np.testing.assert_allclose(
                    np.asarray(cache_c["v"]), np.asarray(cache_b["v"]),
                    rtol=2e-4, atol=2e-4, err_msg=f"{name} chunk={chunk}")
                assert int(cache_c["length"]) == 12

    def test_decode_from_chunked_cache_matches_greedy(self):
        """The serving split — chunked prefill + greedy_decode_with_cache
        — must emit the same tokens as the one-shot greedy_decode."""
        from kubeshare_tpu.models.decoding import (
            greedy_decode, greedy_decode_with_cache, prefill_chunked)
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
            d_ff=64, max_seq_len=32, dtype=jnp.float32,
            attention="reference", positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
        one_shot = greedy_decode(params, config, prompt, 8)
        cache, logits = prefill_chunked(params, config, prompt, 4)
        split = greedy_decode_with_cache(params, config, cache, logits, 8)
        np.testing.assert_array_equal(np.asarray(one_shot),
                                      np.asarray(split))
        # the split path keeps the one-shot path's loud overflow failure
        with pytest.raises(ValueError, match="capacity"):
            greedy_decode_with_cache(params, config, cache, logits, 32)
        # zero/negative generation lengths fail loudly too (ADVICE r4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            greedy_decode_with_cache(params, config, cache, logits, 0)

    def test_jitted_continuation_overflow_caught_with_static_prefill(self):
        """ADVICE r4 (medium): under jit the cache length is traced, so
        the capacity bound can only bind through the static
        ``prefill_length`` — a jitted continuation from a nearly-full
        cache must fail at trace time, not clamp-overwrite the last
        slot."""
        from kubeshare_tpu.models.decoding import (
            greedy_decode_with_cache, prefill)
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=16, dtype=jnp.float32, attention="reference",
            positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, 64)
        cache, logits = prefill(params, config, prompt)

        # 12 prefilled + 8 > 16: the jitted serving pattern
        # (examples/serve_fractional.py) with the static prefill length
        decode_fn = jax.jit(
            lambda c, lg: greedy_decode_with_cache(
                params, config, c, lg, 8, prefill_length=12))
        with pytest.raises(ValueError, match="capacity"):
            decode_fn(cache, logits)
        # with headroom the same jit runs
        ok_fn = jax.jit(
            lambda c, lg: greedy_decode_with_cache(
                params, config, c, lg, 4, prefill_length=12))
        out = ok_fn(cache, logits)
        assert out.shape == (1, 4)
        # outside jit the cache's CONCRETE length stays authoritative: an
        # understated prefill_length must not bypass the real bound
        with pytest.raises(ValueError, match="capacity"):
            greedy_decode_with_cache(params, config, cache, logits, 8,
                                     prefill_length=4)

    def test_sampled_decode_from_cache_matches_one_shot(self):
        """sample_decode == prefill + sample_decode_with_cache under the
        same key (the sampled serving split)."""
        from kubeshare_tpu.models.decoding import (
            prefill, sample_decode, sample_decode_with_cache)
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=32, dtype=jnp.float32, attention="reference")
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        rng = jax.random.PRNGKey(7)
        one_shot = sample_decode(params, config, prompt, rng, 6,
                                 temperature=0.8, top_k=10)
        cache, logits = prefill(params, config, prompt)
        split = sample_decode_with_cache(params, config, cache, logits,
                                         rng, 6, temperature=0.8, top_k=10)
        np.testing.assert_array_equal(np.asarray(one_shot),
                                      np.asarray(split))

    def test_chunked_prefill_ragged_and_chunk_validation(self):
        """Non-tiling prompts no longer raise: the ragged tail runs as
        one bucketed (power-of-two) chunk and must match the bulk
        prefill (tests/test_serving_batching.py locks every remainder); a
        degenerate chunk still fails loudly."""
        from kubeshare_tpu.models.decoding import prefill, prefill_chunked
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
            max_seq_len=32, dtype=jnp.float32, attention="reference")
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 10), 0, 64)
        cache_b, logits_b = prefill(params, config, prompt)
        cache_c, logits_c = prefill_chunked(params, config, prompt, 4)
        np.testing.assert_allclose(
            np.asarray(logits_c), np.asarray(logits_b),
            rtol=2e-4, atol=2e-4)
        assert int(cache_c["length"]) == 10
        with pytest.raises(ValueError, match="chunk"):
            prefill_chunked(params, config, prompt, 0)

    def test_gqa_head_count_validated(self):
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)

        config = TransformerConfig(
            vocab_size=8, d_model=24, n_heads=3, n_kv_heads=2, n_layers=1,
            d_ff=8, max_seq_len=8,
        )
        with pytest.raises(ValueError, match="multiple of n_kv_heads"):
            transformer_init(jax.random.PRNGKey(0), config)

    def test_greedy_decode_jits_and_is_deterministic(self):
        from kubeshare_tpu.models.decoding import greedy_decode

        config, params = self._setup()
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0, 64)
        decode = jax.jit(
            lambda p, t: greedy_decode(p, config, t, max_new_tokens=8)
        )
        out1 = decode(params, prompt)
        out2 = decode(params, prompt)
        assert out1.shape == (2, 8)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
        assert (np.asarray(out1) >= 0).all() and (np.asarray(out1) < 64).all()

    def test_sliding_window_prefill_matches_dense(self):
        """A windowed model must decode with the same band the dense mask
        keeps (ADVICE r1: cached path used to attend over full history)."""
        from dataclasses import replace

        from kubeshare_tpu.models.decoding import (
            prefill_incremental as prefill)

        config, params = self._setup()
        config = replace(config, attention_window=4)
        prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0, 64)
        dense = transformer_apply(params, prompt, config)
        _, last_logits = prefill(params, config, prompt)
        np.testing.assert_allclose(
            np.asarray(dense[:, -1]), np.asarray(last_logits),
            rtol=2e-4, atol=2e-4,
        )
        # and it must differ from the un-windowed decode (mask is live)
        _, full_logits = prefill(params, replace(config, attention_window=None), prompt)
        assert not np.allclose(np.asarray(last_logits), np.asarray(full_logits))

    def test_overflow_guards(self):
        from kubeshare_tpu.models.decoding import greedy_decode, prefill

        config, params = self._setup()
        long_prompt = jnp.zeros((1, 40), jnp.int32)  # > max_seq_len 32
        with pytest.raises(ValueError):
            prefill(params, config, long_prompt)
        with pytest.raises(ValueError):
            greedy_decode(params, config, jnp.zeros((1, 30), jnp.int32), 10)


class TestShardedDecoding:
    """Multi-chip serving: decode with tensor-parallel-placed parameters.
    No decode-specific sharding code needed — the params' NamedShardings
    (transformer_sharding_rules) propagate through the KV-cache scan under
    jit, XLA inserting the tp collectives; these tests pin that the
    sharded path is bit-identical to single-device decode."""

    def _setup(self):
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init, transformer_sharding_rules)
        from kubeshare_tpu.parallel.mesh import shard_params

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=32, dtype=jnp.float32, attention="reference",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        mesh = make_mesh(MeshSpec(dp=2, tp=2, sp=2))
        placed = shard_params(params, transformer_sharding_rules(), mesh)
        return config, params, placed

    def test_tp_sharded_greedy_matches_unsharded(self):
        from kubeshare_tpu.models.decoding import greedy_decode

        config, params, placed = self._setup()
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, 64)
        base = greedy_decode(params, config, prompt, 8)
        sharded = jax.jit(
            lambda p, t: greedy_decode(p, config, t, 8))(placed, prompt)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(sharded))

    def test_tp_sharded_sampling_matches_unsharded(self):
        from kubeshare_tpu.models.decoding import sample_decode

        config, params, placed = self._setup()
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0, 64)
        rng = jax.random.PRNGKey(3)
        base = sample_decode(params, config, prompt, rng, 6,
                             temperature=0.8, top_k=10)
        sharded = jax.jit(lambda p, t, r: sample_decode(
            p, config, t, r, 6, temperature=0.8, top_k=10))(
                placed, prompt, rng)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(sharded))

    def test_gqa_tp_sharded_greedy_matches_unsharded(self):
        """The advertised combination — tp-sharded serving WITH a
        kv_heads-sized cache axis — decoded under placement."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init, transformer_sharding_rules)
        from kubeshare_tpu.parallel.mesh import shard_params

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
            d_ff=64, max_seq_len=32, dtype=jnp.float32,
            attention="reference",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        mesh = make_mesh(MeshSpec(dp=2, tp=2, sp=2))
        placed = shard_params(params, transformer_sharding_rules(), mesh)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, 64)
        base = greedy_decode(params, config, prompt, 8)
        sharded = jax.jit(
            lambda p, t: greedy_decode(p, config, t, 8))(placed, prompt)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(sharded))

    def test_undivisible_tp_names_the_parameter(self):
        """A GQA config whose shrunken wk/wv head axis no longer divides
        tp must fail with the parameter path and axis named, not
        device_put's raw divisibility error."""
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init, transformer_sharding_rules)
        from kubeshare_tpu.parallel.mesh import shard_params

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_kv_heads=1, n_layers=1,
            d_ff=64, max_seq_len=32, dtype=jnp.float32,
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        mesh = make_mesh(MeshSpec(dp=2, tp=2, sp=2))
        with pytest.raises(ValueError, match=r"wk.*axis 1.*tp=2"):
            shard_params(params, transformer_sharding_rules(), mesh)


class TestSpeculativeDecoding:
    """Draft-model speculation must emit EXACTLY greedy_decode's tokens —
    the acceptance rule preserves the target's argmax stream regardless
    of how good or bad the draft is."""

    def _target(self, **extra):
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
            **extra)
        return config, transformer_init(jax.random.PRNGKey(0), config)

    def test_self_draft_matches_greedy(self):
        """Draft == target: every proposal accepted, output identical."""
        from kubeshare_tpu.models.decoding import (
            greedy_decode, speculative_greedy_decode)

        config, params = self._target()
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        base = greedy_decode(params, config, prompt, 12)
        spec = speculative_greedy_decode(
            params, config, params, config, prompt, 12, draft_len=4)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(spec))

    def test_bad_draft_still_matches_greedy(self):
        """A differently-initialized (frequently wrong) draft changes only
        the speed, never the tokens."""
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)
        from kubeshare_tpu.models.decoding import (
            greedy_decode, speculative_greedy_decode)

        config, params = self._target(positional="rope", n_kv_heads=2)
        draft_config = TransformerConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=1, d_ff=32,
            max_seq_len=64, dtype=jnp.float32, attention="reference")
        draft_params = transformer_init(jax.random.PRNGKey(9), draft_config)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        base = greedy_decode(params, config, prompt, 12)
        for draft_len in (2, 3, 5):
            spec = speculative_greedy_decode(
                params, config, draft_params, draft_config, prompt, 12,
                draft_len=draft_len)
            np.testing.assert_array_equal(
                np.asarray(base), np.asarray(spec),
                err_msg=f"draft_len={draft_len}")

    def test_jits(self):
        from kubeshare_tpu.models.decoding import speculative_greedy_decode

        config, params = self._target()
        prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, 64)
        fn = jax.jit(lambda p, t: speculative_greedy_decode(
            p, config, p, config, t, 8))
        out1 = fn(params, prompt)
        out2 = fn(params, prompt)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
        assert out1.shape == (1, 8)

    def test_validation(self):
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)
        from kubeshare_tpu.models.decoding import speculative_greedy_decode

        config, params = self._target()
        prompt = jnp.zeros((1, 4), jnp.int32)
        with pytest.raises(ValueError, match="draft_len"):
            speculative_greedy_decode(params, config, params, config,
                                      prompt, 8, draft_len=1)
        other_vocab = TransformerConfig(
            vocab_size=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
            max_seq_len=64)
        other_params = transformer_init(jax.random.PRNGKey(0), other_vocab)
        with pytest.raises(ValueError, match="vocabular"):
            speculative_greedy_decode(params, config, other_params,
                                      other_vocab, prompt, 8)
        with pytest.raises(ValueError, match="headroom"):
            speculative_greedy_decode(params, config, params, config,
                                      prompt, 60)


class TestSpeculativeSampling:
    """Stochastic speculative decoding (VERDICT r4 #5): the rejection-
    sampling acceptance rule must leave the emitted stream distributed
    EXACTLY as sample_decode's — locked by an empirical distribution-
    equivalence test — while a good draft cuts target passes."""

    def _models(self, vocab=16):
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)

        config = TransformerConfig(
            vocab_size=vocab, d_model=16, n_heads=2, n_layers=1, d_ff=32,
            max_seq_len=32, dtype=jnp.float32, attention="reference")
        params = transformer_init(jax.random.PRNGKey(0), config)
        draft_config = TransformerConfig(
            vocab_size=vocab, d_model=8, n_heads=1, n_layers=1, d_ff=16,
            max_seq_len=32, dtype=jnp.float32, attention="reference")
        draft_params = transformer_init(jax.random.PRNGKey(7), draft_config)
        return config, params, draft_config, draft_params

    def test_distribution_matches_sample_decode(self):
        """Empirical per-position token distributions of the speculative
        sampler and the plain sampler must agree within sampling noise
        (N=1500 lanes; TV tolerance sized ~3x the expected noise — a
        wrong acceptance ratio or residual shifts TV far more)."""
        from kubeshare_tpu.models.decoding import (
            sample_decode, speculative_sample_decode)

        config, params, dconfig, dparams = self._models()
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, 16)
        n, steps = 1500, 3
        keys = jax.random.split(jax.random.PRNGKey(42), n)

        plain = jax.jit(jax.vmap(
            lambda k: sample_decode(params, config, prompt, k, steps,
                                    temperature=0.9, top_k=12)))(keys)
        spec = jax.jit(jax.vmap(
            lambda k: speculative_sample_decode(
                params, config, dparams, dconfig, prompt, k, steps,
                draft_len=3, temperature=0.9, top_k=12)))(keys)
        plain = np.asarray(plain)[:, 0, :]  # [n, steps]
        spec = np.asarray(spec)[:, 0, :]
        for pos in range(steps):
            h_plain = np.bincount(plain[:, pos], minlength=16) / n
            h_spec = np.bincount(spec[:, pos], minlength=16) / n
            tv = 0.5 * np.abs(h_plain - h_spec).sum()
            assert tv < 0.12, (
                f"position {pos}: TV distance {tv:.3f} between plain and "
                f"speculative sampling (plain {h_plain}, spec {h_spec})")

    def test_self_draft_accepts_every_proposal(self):
        """Draft == target makes the acceptance ratio exactly 1: every
        round emits draft_len tokens, so the target-pass count hits the
        theoretical floor ceil((max_new - 1) / draft_len)."""
        from kubeshare_tpu.models.decoding import speculative_sample_decode

        config, params, _, _ = self._models()
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0, 16)
        out, stats = speculative_sample_decode(
            params, config, params, config, prompt,
            jax.random.PRNGKey(3), 12, draft_len=3, return_stats=True)
        assert out.shape == (2, 12)
        assert int(stats["rounds"]) == 4  # ceil(11 / 3)
        # the greedy variant exposes the same stat (benchmarks report
        # measured tokens-per-target-pass rather than assuming accept=1)
        from kubeshare_tpu.models.decoding import speculative_greedy_decode

        gout, gstats = speculative_greedy_decode(
            params, config, params, config, prompt, 12, draft_len=3,
            return_stats=True)
        assert gout.shape == (2, 12)
        assert int(gstats["rounds"]) == 4

    def test_temperature_zero_delegates_to_greedy(self):
        from kubeshare_tpu.models.decoding import (
            greedy_decode, speculative_sample_decode)

        config, params, dconfig, dparams = self._models()
        prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 4), 0, 16)
        spec = speculative_sample_decode(
            params, config, dparams, dconfig, prompt,
            jax.random.PRNGKey(5), 8, temperature=0.0)
        base = greedy_decode(params, config, prompt, 8)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(spec))

    def test_deterministic_under_same_key(self):
        from kubeshare_tpu.models.decoding import speculative_sample_decode

        config, params, dconfig, dparams = self._models()
        prompt = jax.random.randint(jax.random.PRNGKey(6), (2, 4), 0, 16)
        fn = jax.jit(lambda k: speculative_sample_decode(
            params, config, dparams, dconfig, prompt, k, 10, draft_len=4,
            top_p=0.95))
        k = jax.random.PRNGKey(8)
        np.testing.assert_array_equal(np.asarray(fn(k)), np.asarray(fn(k)))

    def test_validation(self):
        from kubeshare_tpu.models.decoding import speculative_sample_decode

        config, params, dconfig, dparams = self._models()
        prompt = jnp.zeros((1, 4), jnp.int32)
        rng = jax.random.PRNGKey(0)
        with pytest.raises(ValueError, match="max_new_tokens"):
            speculative_sample_decode(params, config, dparams, dconfig,
                                      prompt, rng, 0)
        with pytest.raises(ValueError, match="draft_len"):
            speculative_sample_decode(params, config, dparams, dconfig,
                                      prompt, rng, 8, draft_len=1)
        with pytest.raises(ValueError, match="temperature"):
            speculative_sample_decode(params, config, dparams, dconfig,
                                      prompt, rng, 8, temperature=-1.0)


class TestSampledDecoding:
    _setup = TestDecoding._setup

    def test_temperature_zero_is_greedy(self):
        from kubeshare_tpu.models.decoding import greedy_decode, sample_decode

        config, params = self._setup()
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0, 64)
        greedy = greedy_decode(params, config, prompt, max_new_tokens=8)
        sampled = sample_decode(params, config, prompt,
                                jax.random.PRNGKey(7), 8, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(greedy), np.asarray(sampled))

    def test_top_k_one_is_greedy(self):
        from kubeshare_tpu.models.decoding import greedy_decode, sample_decode

        config, params = self._setup()
        prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 4), 0, 64)
        greedy = greedy_decode(params, config, prompt, max_new_tokens=6)
        sampled = sample_decode(params, config, prompt,
                                jax.random.PRNGKey(9), 6, temperature=1.0,
                                top_k=1)
        np.testing.assert_array_equal(np.asarray(greedy), np.asarray(sampled))

    def test_jit_deterministic_under_same_key(self):
        from kubeshare_tpu.models.decoding import sample_decode

        config, params = self._setup()
        prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 4), 0, 64)
        decode = jax.jit(lambda p, t, r: sample_decode(
            p, config, t, r, 8, temperature=0.8, top_k=10, top_p=0.9))
        out1 = decode(params, prompt, jax.random.PRNGKey(5))
        out2 = decode(params, prompt, jax.random.PRNGKey(5))
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
        assert out1.shape == (2, 8)
        assert (np.asarray(out1) >= 0).all() and (np.asarray(out1) < 64).all()
        # a different key must be able to produce a different sequence
        out3 = decode(params, prompt, jax.random.PRNGKey(6))
        assert not np.array_equal(np.asarray(out1), np.asarray(out3))

    def test_filter_logits_top_k(self):
        from kubeshare_tpu.models.decoding import _filter_logits

        logits = jnp.asarray([[1.0, 5.0, 3.0, 2.0]])
        out = np.asarray(_filter_logits(logits, top_k=2, top_p=None))
        assert np.isfinite(out[0, 1]) and np.isfinite(out[0, 2])
        assert np.isneginf(out[0, 0]) and np.isneginf(out[0, 3])
        # top_k >= vocab keeps everything (explicit clamp, ADVICE r2)
        out = np.asarray(_filter_logits(logits, top_k=100, top_p=None))
        assert np.isfinite(out).all()

    def test_filter_logits_top_p(self):
        from kubeshare_tpu.models.decoding import _filter_logits

        # softmax of [2, 1, 0, -10] ~= [0.70, 0.26, 0.095, ~0]: top_p=0.5
        # keeps only the first (its mass alone reaches 0.5)
        logits = jnp.asarray([[2.0, 1.0, 0.0, -10.0]])
        out = np.asarray(_filter_logits(logits, top_k=None, top_p=0.5))
        assert np.isfinite(out[0, 0])
        assert np.isneginf(out[0, 1:]).all()
        # top_p=1.0 keeps everything
        out = np.asarray(_filter_logits(logits, top_k=None, top_p=1.0))
        assert np.isfinite(out).all()

    def test_argument_validation(self):
        from kubeshare_tpu.models.decoding import _filter_logits, sample_decode

        config, params = self._setup()
        with pytest.raises(ValueError):
            sample_decode(params, config, jnp.zeros((1, 4), jnp.int32),
                          jax.random.PRNGKey(0), 8, temperature=-1.0)
        with pytest.raises(ValueError):
            sample_decode(params, config, jnp.zeros((1, 30), jnp.int32),
                          jax.random.PRNGKey(0), 10)
        with pytest.raises(ValueError):
            _filter_logits(jnp.zeros((1, 4)), top_k=0, top_p=None)
        with pytest.raises(ValueError):
            _filter_logits(jnp.zeros((1, 4)), top_k=None, top_p=1.5)
