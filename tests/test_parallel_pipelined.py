"""The pipelined transformer (GPipe forward, 1F1B training) on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh


class TestPipelinedTransformer:
    def test_matches_dense_forward(self):
        from kubeshare_tpu.models.transformer import (
            TransformerConfig,
            transformer_apply,
            transformer_apply_pipelined,
            transformer_init,
        )

        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("pp",))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
            max_seq_len=32, dtype=jnp.float32, attention="reference",
            positional="rope",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
        dense = transformer_apply(params, tokens, config)
        piped = transformer_apply_pipelined(params, tokens, config, mesh,
                                            num_microbatches=2)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(piped),
                                   rtol=2e-4, atol=2e-4)

    def test_pipelined_grads_flow(self):
        from kubeshare_tpu.models.transformer import (
            TransformerConfig,
            transformer_apply_pipelined,
            transformer_init,
        )

        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("pp",))
        config = TransformerConfig(
            vocab_size=32, d_model=16, n_heads=2, n_layers=2, d_ff=32,
            max_seq_len=16, dtype=jnp.float32, attention="reference",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jnp.ones((2, 8), jnp.int32)

        def loss(params):
            return transformer_apply_pipelined(
                params, tokens, config, mesh, num_microbatches=2).sum()

        grads = jax.jit(jax.grad(loss))(params)
        flat = jax.tree.leaves(grads)
        assert all(np.isfinite(np.asarray(g)).all() for g in flat)
        assert sum(float(np.abs(np.asarray(g)).sum()) for g in flat) > 0


class TestTransformerTrain1F1B:
    """transformer_train_1f1b: the FULL flagship training step under the
    1F1B schedule — loss and grads for every parameter (embedding,
    positional, all layers, final norm, lm_head) must be gradient-
    equivalent to autodiff over the dense forward."""

    @staticmethod
    def _reference(params, tokens, targets, config):
        from kubeshare_tpu.models.transformer import transformer_apply
        from kubeshare_tpu.parallel.train import cross_entropy_loss

        def loss(p):
            return cross_entropy_loss(
                transformer_apply(p, tokens, config), targets)

        return jax.jit(jax.value_and_grad(loss))(params)

    @pytest.mark.parametrize("positional", ["learned", "rope"])
    def test_matches_dense_autodiff(self, positional):
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init, transformer_train_1f1b)

        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("pp",))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
            max_seq_len=32, dtype=jnp.float32, attention="reference",
            positional=positional,
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
        targets = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 64)

        loss, grads = transformer_train_1f1b(
            params, tokens, targets, config, mesh, num_microbatches=2)
        loss_ref, grads_ref = self._reference(params, tokens, targets, config)

        np.testing.assert_allclose(float(loss), float(loss_ref),
                                   rtol=1e-5, atol=1e-6)
        flat, flat_ref = jax.tree.leaves(grads), jax.tree.leaves(grads_ref)
        assert len(flat) == len(flat_ref)
        for g, g_ref in zip(flat, flat_ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                       rtol=2e-4, atol=2e-5)

    def test_1f1b_sp_ring_matches_dense_autodiff(self):
        """1F1B x sp with ring attention in-stage — the flagship schedule:
        gradients still match dense autodiff, every param included."""
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init, transformer_train_1f1b)

        pp, sp = 2, 2
        mesh = Mesh(np.array(jax.devices()[:pp * sp]).reshape(pp, sp),
                    ("pp", "sp"))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
            max_seq_len=32, dtype=jnp.float32, attention="ring",
            positional="rope",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, 64)
        targets = jax.random.randint(jax.random.PRNGKey(4), (4, 16), 0, 64)

        loss, grads = transformer_train_1f1b(
            params, tokens, targets, config, mesh, num_microbatches=2)
        dense_config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
            max_seq_len=32, dtype=jnp.float32, attention="reference",
            positional="rope",
        )
        loss_ref, grads_ref = self._reference(
            params, tokens, targets, dense_config)

        np.testing.assert_allclose(float(loss), float(loss_ref),
                                   rtol=1e-5, atol=1e-6)
        for g, g_ref in zip(jax.tree.leaves(grads),
                            jax.tree.leaves(grads_ref)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                       rtol=5e-4, atol=5e-5)

    def test_1f1b_sp_ulysses_runs(self):
        """Ulysses all-to-all in-stage under 1F1B: finite loss + grads."""
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init, transformer_train_1f1b)

        pp, sp = 2, 2
        mesh = Mesh(np.array(jax.devices()[:pp * sp]).reshape(pp, sp),
                    ("pp", "sp"))
        config = TransformerConfig(
            vocab_size=32, d_model=16, n_heads=2, n_layers=2, d_ff=32,
            max_seq_len=16, dtype=jnp.float32, attention="ulysses",
            positional="rope",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jnp.ones((2, 8), jnp.int32)

        loss, grads = transformer_train_1f1b(
            params, tokens, tokens, config, mesh, num_microbatches=2)
        assert np.isfinite(float(loss))
        flat = jax.tree.leaves(grads)
        assert all(np.isfinite(np.asarray(g)).all() for g in flat)
        assert sum(float(np.abs(np.asarray(g)).sum()) for g in flat) > 0
