"""Compute-path tests: the attention kernels on one device (flash forward
and backward, block-sparse, sliding window, GQA, rope)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models import (
    TransformerConfig,
    transformer_apply,
    transformer_init,
)
from kubeshare_tpu.ops import attention_reference, flash_attention
from kubeshare_tpu.parallel import MeshSpec, make_mesh

from compute_helpers import rand


class TestAttention:
    def test_flash_matches_reference_interpret(self):
        q, k, v = (rand(i, 2, 4, 64, 16) for i in range(3))
        ref = attention_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=32,
                              use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-4, atol=2e-4)

    def test_flash_non_causal(self):
        q, k, v = (rand(i, 1, 2, 32, 8) for i in range(3))
        ref = attention_reference(q, k, v, causal=False)
        out = flash_attention(q, k, v, causal=False, block_q=16,
                              use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-4, atol=2e-4)

    def test_flash_gradients(self):
        q, k, v = (rand(i, 1, 2, 32, 8) for i in range(3))

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, use_pallas=True, interpret=True,
                                   block_q=16).sum()

        def loss_ref(q, k, v):
            return attention_reference(q, k, v).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_cpu_auto_fallback(self):
        q, k, v = (rand(i, 1, 1, 16, 8) for i in range(3))
        out = flash_attention(q, k, v)  # auto: CPU -> reference
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)

    def test_default_blocks_by_seq_len(self):
        """Seq-dependent kernel tiles (v5e sweep, docs/perf.md): larger
        blocks only at s >= 8192 AND only when they tile — an untiled
        pick would silently demote the call to the XLA reference."""
        from kubeshare_tpu.ops.attention import default_blocks

        assert default_blocks(2048) == (512, 1024)
        assert default_blocks(8192) == (1024, 2048)
        assert default_blocks(16384) == (1024, 2048)
        assert default_blocks(9216) == (512, 1024)  # 9216 % 2048 != 0


class TestBlockSparseAttention:
    """Arbitrary [n_qblocks, n_kblocks] masks over the flash kernels
    (document masking / prefix-LM / strided sparsity): the mask rides in
    SMEM and masked tiles are skipped in forward AND both backward
    sweeps."""

    BQ = BK = 16

    def _mask(self, nq, nk, seed=0, density=0.6):
        rng = np.random.default_rng(seed)
        mask = (rng.random((nq, nk)) < density).astype(np.int32)
        mask[0, 0] = 1  # at least one live tile
        return mask

    def test_matches_reference(self):
        from kubeshare_tpu.ops.attention import (block_sparse_attention,
                                                 block_sparse_reference)

        q, k, v = (rand(i, 2, 2, 64, 16) for i in range(3))
        mask = self._mask(4, 4)
        ref = block_sparse_reference(q, k, v, jnp.asarray(mask), True,
                                     self.BQ, self.BK)
        out = block_sparse_attention(q, k, v, mask, causal=True,
                                     block_q=self.BQ, block_k=self.BK,
                                     use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_gradients_match_reference(self):
        from kubeshare_tpu.ops.attention import (block_sparse_attention,
                                                 block_sparse_reference)

        q, k, v = (rand(i, 1, 2, 32, 8) for i in range(3))
        mask = self._mask(2, 2, seed=1, density=0.8)

        def loss_kernel(q, k, v):
            return (block_sparse_attention(
                q, k, v, mask, causal=True, block_q=self.BQ,
                block_k=self.BK, use_pallas=True, interpret=True) ** 2).sum()

        def loss_ref(q, k, v):
            return (block_sparse_reference(
                q, k, v, jnp.asarray(mask), True, self.BQ, self.BK) ** 2).sum()

        g_kernel = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_kernel, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_gqa_heads_share_mask(self):
        from kubeshare_tpu.ops.attention import (block_sparse_attention,
                                                 block_sparse_reference)

        q = rand(0, 1, 4, 64, 16)
        k, v = (rand(i, 1, 2, 64, 16) for i in (1, 2))
        mask = self._mask(4, 4, seed=2, density=0.7)
        ref = block_sparse_reference(q, k, v, jnp.asarray(mask), True,
                                     self.BQ, self.BK)
        out = block_sparse_attention(q, k, v, mask, causal=True,
                                     block_q=self.BQ, block_k=self.BK,
                                     use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_fully_masked_rows_zero(self):
        from kubeshare_tpu.ops.attention import block_sparse_attention

        q, k, v = (rand(i, 1, 1, 64, 8) for i in range(3))
        mask = np.ones((4, 4), np.int32)
        mask[2, :] = 0  # q-block 2 attends nothing
        out = block_sparse_attention(q, k, v, mask, causal=False,
                                     block_q=self.BQ, block_k=self.BK,
                                     use_pallas=True, interpret=True)
        rows = np.asarray(out)[:, :, 2 * self.BQ:3 * self.BQ, :]
        assert np.all(rows == 0)
        assert not np.any(np.isnan(np.asarray(out)))

    def test_mask_shape_validated(self):
        from kubeshare_tpu.ops.attention import block_sparse_attention

        q, k, v = (rand(i, 1, 1, 64, 8) for i in range(3))
        with pytest.raises(ValueError, match="block_mask shape"):
            block_sparse_attention(q, k, v, np.ones((3, 4), np.int32),
                                   block_q=self.BQ, block_k=self.BK,
                                   use_pallas=True, interpret=True)


class TestFlashKTiling:
    def test_multiple_k_blocks(self):
        from kubeshare_tpu.ops.attention import _flash_forward

        q, k, v = (rand(i, 1, 2, 64, 8) for i in range(3))
        for causal in (True, False):
            ref = attention_reference(q, k, v, causal)
            out, lse = _flash_forward(q, k, v, causal, block_q=16,
                                      interpret=True, block_k=16)
            assert lse.shape == q.shape[:3] + (1,)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                       rtol=2e-4, atol=2e-4)

    def test_k_tiling_gradients(self):
        q, k, v = (rand(i, 1, 1, 32, 8) for i in range(3))

        def loss(q, k, v):
            return flash_attention(q, k, v, block_q=8, use_pallas=True,
                                   interpret=True).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: attention_reference(q, k, v).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


class TestFlashBackwardKernels:
    def test_grads_multi_block_causal_and_not(self):
        q, k, v = (rand(i, 2, 2, 64, 8) for i in range(3))
        for causal in (True, False):
            def loss(q, k, v):
                return (flash_attention(q, k, v, causal=causal, block_q=16,
                                        use_pallas=True, interpret=True) ** 2).sum()

            def loss_ref(q, k, v):
                return (attention_reference(q, k, v, causal) ** 2).sum()

            g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(g, g_ref):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-4, atol=2e-4)

    def test_value_and_grad_through_training_loss(self):
        # end-to-end: attention inside a toy loss with value_and_grad
        q, k, v = (rand(i, 1, 2, 32, 8) for i in range(3))
        targets = rand(9, 1, 2, 32, 8)

        def loss(q, k, v):
            out = flash_attention(q, k, v, block_q=8, use_pallas=True,
                                  interpret=True)
            return jnp.mean((out - targets) ** 2)

        (val, grads) = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        assert np.isfinite(float(val))
        for g in grads:
            assert np.isfinite(np.asarray(g)).all()


class TestFlashBackwardFallback:
    def test_non_tiling_seq_uses_reference_grads(self):
        # s=320 tiles the forward blocks (bq=64, bk=min(1024,320)=320) but
        # not the backward defaults (256/512): must fall back, not truncate
        q, k, v = (rand(i, 1, 2, 320, 8) for i in range(3))

        def loss(q, k, v):
            return flash_attention(q, k, v, block_q=64, use_pallas=True,
                                   interpret=True).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: attention_reference(q, k, v).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            assert np.isfinite(np.asarray(a)).all()
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


class TestSlidingWindowAttention:
    def test_window_matches_reference(self):
        q, k, v = (rand(i, 1, 2, 64, 8) for i in range(3))
        for window in (8, 16, 64):
            ref = attention_reference(q, k, v, causal=True, window=window)
            out = flash_attention(q, k, v, block_q=16, use_pallas=True,
                                  interpret=True, window=window)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                       rtol=2e-4, atol=2e-4)

    def test_window_gradients(self):
        q, k, v = (rand(i, 1, 1, 32, 8) for i in range(3))

        def loss(q, k, v):
            return flash_attention(q, k, v, block_q=8, use_pallas=True,
                                   interpret=True, window=8).sum()

        def loss_ref(q, k, v):
            return attention_reference(q, k, v, True, window=8).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_window_equals_full_causal(self):
        # window >= seq is exactly causal attention
        q, k, v = (rand(i, 1, 1, 32, 8) for i in range(3))
        full = attention_reference(q, k, v, causal=True)
        windowed = flash_attention(q, k, v, block_q=8, use_pallas=True,
                                   interpret=True, window=32)
        np.testing.assert_allclose(np.asarray(full), np.asarray(windowed),
                                   rtol=2e-4, atol=2e-4)

    def test_window_with_multiple_k_blocks(self):
        # force several K blocks so the band-skip clause actually runs
        from kubeshare_tpu.ops.attention import _flash_forward

        q, k, v = (rand(i, 1, 2, 64, 8) for i in range(3))
        for window in (8, 24, 40):
            ref = attention_reference(q, k, v, causal=True, window=window)
            out, _ = _flash_forward(q, k, v, True, 16, True, block_k=16,
                                    window=window)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                       rtol=2e-4, atol=2e-4)

    def test_window_backward_multiple_blocks(self):
        # s=1024 -> bwd blocks 256/512: several blocks in both sweeps
        q, k, v = (rand(i, 1, 1, 1024, 8) for i in range(3))

        def loss(q, k, v):
            return flash_attention(q, k, v, use_pallas=True, interpret=True,
                                   window=300).sum()

        def loss_ref(q, k, v):
            return attention_reference(q, k, v, True, window=300).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)

    def test_invalid_window_rejected(self):
        q = rand(0, 1, 1, 16, 8)
        with pytest.raises(ValueError):
            flash_attention(q, q, q, window=0)
        with pytest.raises(ValueError):
            attention_reference(q, q, q, window=-5)


class TestGQA:
    def test_gqa_matches_repeated_reference(self):
        q = rand(0, 1, 8, 64, 16)
        k = rand(1, 1, 2, 64, 16)  # 2 kv heads, group of 4
        v = rand(2, 1, 2, 64, 16)
        k_full = jnp.repeat(k, 4, axis=1)
        v_full = jnp.repeat(v, 4, axis=1)
        ref = attention_reference(q, k_full, v_full, causal=True)
        out = flash_attention(q, k, v, block_q=16, use_pallas=True,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-4, atol=2e-4)

    def test_gqa_gradients(self):
        q = rand(0, 1, 4, 32, 8)
        k = rand(1, 1, 2, 32, 8)
        v = rand(2, 1, 2, 32, 8)

        def loss(q, k, v):
            return flash_attention(q, k, v, block_q=8, use_pallas=True,
                                   interpret=True).sum()

        def loss_ref(q, k, v):
            return attention_reference(
                q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)
            ).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        # reference grads for grouped kv: sum over the repeat
        gq_ref, gk_full, gv_full = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(g[0]), np.asarray(gq_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(g[1]), np.asarray(gk_full),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(g[2]), np.asarray(gv_full),
                                   rtol=1e-4, atol=1e-4)

    def test_bad_head_ratio_rejected(self):
        q = rand(0, 1, 6, 16, 8)
        k = rand(1, 1, 4, 16, 8)
        with pytest.raises(ValueError):
            flash_attention(q, k, k, block_q=8, use_pallas=True, interpret=True)


class TestRope:
    def test_rope_shapes_and_rotation_identity(self):
        from kubeshare_tpu.ops.rope import apply_rope, rope_positions

        x = rand(0, 2, 4, 16, 8)
        out = apply_rope(x, rope_positions(16))
        assert out.shape == x.shape
        # position 0 is the identity rotation
        np.testing.assert_allclose(np.asarray(out[:, :, 0]),
                                   np.asarray(x[:, :, 0]), rtol=1e-5)
        # rotation preserves pair norms
        def pair_norms(a):
            a1, a2 = np.split(np.asarray(a, np.float64), 2, axis=-1)
            return a1**2 + a2**2
        np.testing.assert_allclose(pair_norms(out), pair_norms(x), rtol=1e-4)

    def test_rope_relative_shift_invariance(self):
        from kubeshare_tpu.ops.rope import apply_rope, rope_positions

        # attention scores depend only on relative positions
        q = rand(0, 1, 1, 8, 8)
        k = rand(1, 1, 1, 8, 8)
        def scores(offset):
            pos = rope_positions(8, offset)
            qr, kr = apply_rope(q, pos), apply_rope(k, pos)
            return np.asarray(jnp.einsum("bhqd,bhkd->bhqk", qr, kr))
        np.testing.assert_allclose(scores(0), scores(17), rtol=1e-4, atol=1e-5)

    def test_rope_transformer_and_decode_consistent(self):
        from kubeshare_tpu.models.decoding import (
            prefill_incremental as prefill)

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=32, dtype=jnp.float32, attention="reference",
            positional="rope",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
        dense = transformer_apply(params, prompt, config)
        _, last_logits = prefill(params, config, prompt)
        np.testing.assert_allclose(np.asarray(dense[:, -1]),
                                   np.asarray(last_logits),
                                   rtol=2e-4, atol=2e-4)

    def test_rope_ring_matches_dense(self):
        from kubeshare_tpu.models.transformer import transformer_apply_ring

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
            positional="rope",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
        dense = transformer_apply(params, tokens, config)
        ring = transformer_apply_ring(params, tokens, config, mesh)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                                   rtol=2e-4, atol=2e-4)

    def test_rope_config_validation_and_no_pos_table(self):
        config = TransformerConfig(
            vocab_size=16, d_model=16, n_heads=2, n_layers=1, d_ff=16,
            max_seq_len=16, dtype=jnp.float32, attention="reference",
            positional="rope",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        assert "pos_embed" not in params  # no dead table under rope
        bad = TransformerConfig(
            vocab_size=16, d_model=16, n_heads=2, n_layers=1, d_ff=16,
            max_seq_len=16, dtype=jnp.float32, positional="Rotary",
        )
        with pytest.raises(ValueError):
            transformer_init(jax.random.PRNGKey(0), bad)
