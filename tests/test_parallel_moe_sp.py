"""Token-choice MoE under the sequence-parallel entries on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.parallel import MeshSpec, make_mesh


class TestMoESequenceParallel:
    """MoE layers on the standalone ring/ulysses entries (round 4):
    routing is per-token, so each sequence shard routes locally with
    shard-derived expert buffers; at no-drop capacities the output must
    equal the dense entry exactly."""

    def _setup(self, **extra):
        from kubeshare_tpu.models.transformer import (
            TransformerConfig, transformer_init)

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
            moe_every=2, moe_num_experts=4, moe_top_k=2,
            # generous capacity: no drops on either the global (dense) or
            # the per-shard derivation, so outputs are exactly comparable
            moe_capacity_factor=4.0, **extra)
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
        return config, params, tokens

    def test_moe_ring_matches_dense(self):
        from kubeshare_tpu.models.transformer import (
            transformer_apply, transformer_apply_ring)

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config, params, tokens = self._setup()
        dense = transformer_apply(params, tokens, config)
        ring = transformer_apply_ring(params, tokens, config, mesh)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                                   rtol=2e-4, atol=2e-4)

    def test_moe_ulysses_matches_dense_with_aux(self):
        from kubeshare_tpu.models.transformer import (
            transformer_apply, transformer_apply_ulysses)

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config, params, tokens = self._setup()
        dense = transformer_apply(params, tokens, config)
        out, aux = transformer_apply_ulysses(params, tokens, config, mesh,
                                             with_aux=True)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(out),
                                   rtol=2e-4, atol=2e-4)
        # the sp-mean aux estimator is a usable load-balancing signal
        assert np.isfinite(float(aux)) and float(aux) > 0

    def test_moe_zigzag_ring_matches_dense(self):
        from kubeshare_tpu.models.transformer import (
            transformer_apply, transformer_apply_ring)

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config, params, tokens = self._setup(positional="rope")
        dense = transformer_apply(params, tokens, config)
        ring = transformer_apply_ring(params, tokens, config, mesh,
                                      layout="zigzag", use_flash=False)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                                   rtol=2e-4, atol=2e-4)

    def test_experts_choose_rejected_on_sp_entries(self):
        """Expert-choice routing is whole-batch routing — a sequence
        shard cannot route it locally (per-shard selection materially
        diverges from the dense entry), so the sp entries refuse it."""
        from kubeshare_tpu.models.transformer import (
            transformer_apply_ring, transformer_init)

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config, params, tokens = self._setup()
        from dataclasses import replace

        ec = replace(config, moe_routing="experts_choose")
        ec_params = transformer_init(jax.random.PRNGKey(0), ec)
        with pytest.raises(ValueError, match="whole-batch"):
            transformer_apply_ring(ec_params, tokens, ec, mesh)

    def test_moe_ring_grads_flow(self):
        from kubeshare_tpu.models.transformer import transformer_apply_ring
        from kubeshare_tpu.parallel.train import cross_entropy_loss

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config, params, tokens = self._setup()

        def loss(p):
            logits, aux = transformer_apply_ring(
                p, tokens, config, mesh, with_aux=True)
            return cross_entropy_loss(logits, tokens) + 0.01 * aux

        grads = jax.jit(jax.grad(loss))(params)
        g = np.asarray(grads["layers"][1]["moe"]["w_in"])
        assert np.isfinite(g).all() and np.abs(g).sum() > 0
