"""Pipeline parallelism composed with sequence parallelism on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from kubeshare_tpu.parallel.pipeline import pipeline_apply, stack_stage_params


class TestPipelineSequenceParallel:
    """pp x sp composition: sequence-parallel attention (ring / Ulysses)
    running INSIDE pipeline stages — activations flow sequence-sharded,
    microbatches hop stages over pp, attention collectives run over sp."""

    def _mesh(self, pp=2, sp=4):
        devices = np.array(jax.devices()[:pp * sp]).reshape(pp, sp)
        return Mesh(devices, ("pp", "sp"))

    def _config(self, attention, **kw):
        from kubeshare_tpu.models.transformer import TransformerConfig

        return TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention=attention,
            positional="rope", **kw)

    def _check_matches_dense(self, attention, **kw):
        from dataclasses import replace

        from kubeshare_tpu.models.transformer import (
            transformer_apply, transformer_apply_pipelined, transformer_init)

        mesh = self._mesh()
        config = self._config(attention, **kw)
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 64)
        dense = transformer_apply(
            params, tokens, replace(config, attention="reference"))
        piped = transformer_apply_pipelined(
            params, tokens, config, mesh, num_microbatches=2)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(piped),
                                   rtol=2e-4, atol=2e-4)

    def test_ring_in_pipeline_matches_dense(self):
        self._check_matches_dense("ring")

    def test_ulysses_in_pipeline_matches_dense(self):
        self._check_matches_dense("ulysses")

    def test_windowed_ulysses_in_pipeline(self):
        self._check_matches_dense("ulysses", attention_window=8)

    def test_moe_still_rejected_on_pipelined_path(self):
        from kubeshare_tpu.models.transformer import (
            transformer_apply_pipelined, transformer_init)

        mesh = self._mesh()
        config = self._config("ring", moe_every=2, moe_num_experts=4)
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jnp.zeros((4, 32), jnp.int32)
        with pytest.raises(ValueError, match="MoE"):
            transformer_apply_pipelined(params, tokens, config, mesh,
                                        num_microbatches=2)


    def test_windowed_ring_in_pipeline(self):
        """Sliding-window attention through the in-stage einsum ring
        (round 4: the ring path composes with windows now)."""
        self._check_matches_dense("ring", attention_window=8)

    def test_grads_flow_through_pp_sp(self):
        from kubeshare_tpu.models.transformer import (
            transformer_apply_pipelined, transformer_init)

        mesh = self._mesh()
        config = self._config("ring")
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jnp.ones((4, 32), jnp.int32)
        grads = jax.jit(jax.grad(lambda p: transformer_apply_pipelined(
            p, tokens, config, mesh, num_microbatches=2).sum()))(params)
        flat = jax.tree_util.tree_leaves(grads)
        assert all(np.isfinite(np.asarray(g)).all() for g in flat)
        assert any(np.abs(np.asarray(g)).sum() > 0 for g in flat)

    def test_missing_sp_axis_raises(self):
        from kubeshare_tpu.models.transformer import (
            transformer_apply_pipelined, transformer_init)

        devices = np.array(jax.devices()[:2]).reshape(2)
        mesh = Mesh(devices, ("pp",))
        config = self._config("ring")
        params = transformer_init(jax.random.PRNGKey(0), config)
        with pytest.raises(ValueError, match="mesh axis"):
            transformer_apply_pipelined(params, jnp.ones((2, 16), jnp.int32),
                                        config, mesh)

    def test_activation_spec_rejects_pp(self):
        mesh = self._mesh()
        stage_params = {"w": jnp.zeros((2, 4, 4))}
        with pytest.raises(ValueError, match="must not shard"):
            pipeline_apply(stage_params, jnp.zeros((4, 8, 4)),
                           lambda p, x: x, mesh, 2,
                           activation_spec=P("pp", None, None))


    def test_ring_flash_in_pipeline_matches_dense(self):
        """The Pallas-fused ring body (interpret mode) inside pipeline
        stages — the pp x sp kernel path."""
        from dataclasses import replace

        from kubeshare_tpu.models.transformer import (
            transformer_apply, transformer_apply_pipelined, transformer_init)

        mesh = self._mesh()
        config = self._config("ring")
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 32), 0, 64)
        dense = transformer_apply(
            params, tokens, replace(config, attention="reference"))
        piped = transformer_apply_pipelined(
            params, tokens, config, mesh, num_microbatches=2,
            use_flash=True, interpret=True)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(piped),
                                   rtol=2e-4, atol=2e-4)

    def test_1f1b_composes_with_sp(self):
        """1F1B x sp: ring attention inside the stage body, losses pmean'd
        and param grads psum'd over sp — gradient-equivalent to autodiff
        over the sp-composed GPipe path."""
        from kubeshare_tpu.ops.ring_attention import ring_attention
        from kubeshare_tpu.parallel.pipeline import (
            pipeline_apply, pipeline_train_1f1b, stack_stage_params)

        pp, sp = 2, 4
        devices = np.array(jax.devices()[:pp * sp]).reshape(pp, sp)
        mesh = Mesh(devices, ("pp", "sp"))
        d = 8
        rng = jax.random.PRNGKey(0)
        stacked = stack_stage_params([
            {"w": jax.random.normal(jax.random.fold_in(rng, s), (d, d)) * 0.3}
            for s in range(pp)
        ])
        x = jax.random.normal(jax.random.fold_in(rng, 10), (4, 32, d))
        y = jax.random.normal(jax.random.fold_in(rng, 11), (4, 32, d))
        spec = P(None, "sp", None)

        def stage_fn(params, xin):
            # toy attention stage: single head over the sequence shard
            h = (xin @ params["w"])[:, None]  # [mb, 1, s_local, d]
            att = ring_attention(h, h, h, axis_name="sp", causal=True)
            return xin + att[:, 0]

        def loss_fn(out, target):
            return jnp.mean((out - target.astype(out.dtype)) ** 2)

        loss_1f1b, grads_1f1b = pipeline_train_1f1b(
            stacked, x, y, stage_fn, loss_fn, mesh, num_microbatches=2,
            activation_spec=spec, target_spec=spec)

        def gpipe_loss(params):
            out = pipeline_apply(params, x, stage_fn, mesh, 2,
                                 activation_spec=spec)
            return jnp.mean((out.astype(jnp.float32) - y) ** 2)

        loss_ref, grads_ref = jax.jit(jax.value_and_grad(gpipe_loss))(stacked)
        np.testing.assert_allclose(float(loss_1f1b), float(loss_ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(grads_1f1b["w"]),
                                   np.asarray(grads_ref["w"]),
                                   rtol=1e-4, atol=1e-4)

    def test_1f1b_sp_with_token_targets(self):
        """Default target spec truncates the activation spec to y's rank
        ([batch, seq] int targets vs [batch, seq, d] activations)."""
        from kubeshare_tpu.parallel.pipeline import (
            pipeline_train_1f1b, stack_stage_params)

        pp, sp = 2, 2
        devices = np.array(jax.devices()[:pp * sp]).reshape(pp, sp)
        mesh = Mesh(devices, ("pp", "sp"))
        d, vocab = 8, 16
        rng = jax.random.PRNGKey(0)
        stacked = stack_stage_params([
            {"w": jax.random.normal(jax.random.fold_in(rng, s), (d, d)) * 0.3}
            for s in range(pp)
        ])
        x = jax.random.normal(jax.random.fold_in(rng, 5), (4, 8, d))
        y = jax.random.randint(jax.random.fold_in(rng, 6), (4, 8), 0, vocab)
        proj = jax.random.normal(jax.random.fold_in(rng, 7), (d, vocab))

        def stage_fn(params, xin):
            return xin + jax.nn.gelu(xin @ params["w"])

        def loss_fn(out, target):
            logits = out @ proj.astype(out.dtype)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            onehot = jax.nn.one_hot(target, vocab)
            return -jnp.mean(jnp.sum(onehot * logp, axis=-1))

        loss, grads = pipeline_train_1f1b(
            stacked, x, y, stage_fn, loss_fn, mesh, num_microbatches=2,
            activation_spec=P(None, "sp", None))
        assert np.isfinite(float(loss))
        assert np.isfinite(np.asarray(grads["w"])).all()
