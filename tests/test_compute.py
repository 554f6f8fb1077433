"""Compute-path tests on the 8-device CPU mesh: model forwards/training,
mesh shardings, and the sequence-parallel (ring, Ulysses) transformers
against the dense forward.

The rest of the compute path is a file a layer (``tests/test_compute_*.py``:
attention kernels, ring / zigzag / Ulysses attention, capacity MoE, the
dense-cache decoders) because a tier-1 worker holds a file for its whole
length (``--dist loadfile``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from kubeshare_tpu.models import (
    MnistConfig,
    ResNetConfig,
    TransformerConfig,
    mnist_apply,
    mnist_init,
    resnet_apply,
    resnet_init,
    transformer_apply,
    transformer_init,
)
from kubeshare_tpu.models.transformer import transformer_sharding_rules
from kubeshare_tpu.parallel import MeshSpec, batch_sharding, make_mesh
from kubeshare_tpu.parallel.mesh import shard_params
from kubeshare_tpu.parallel.train import cross_entropy_loss, make_train_step

from compute_helpers import rand


class TestModels:
    def test_mnist_forward_and_train(self):
        config = MnistConfig()
        params = mnist_init(jax.random.PRNGKey(0), config)
        images = rand(1, 8, 28, 28, 1)
        logits = mnist_apply(params, images)
        assert logits.shape == (8, 10)

        init_state, train_step = make_train_step(
            mnist_apply,
            loss_fn=lambda logits, y: cross_entropy_loss(logits, y),
        )
        state = init_state(params)
        labels = jnp.zeros((8,), jnp.int32)
        losses = []
        for _ in range(5):
            state, loss = train_step(state, images, labels)
            losses.append(float(loss))
        assert losses[-1] < losses[0]  # it learns the constant label

    def test_resnet_forward(self):
        config = ResNetConfig(widths=(8, 16), blocks_per_stage=(1, 1))
        params = resnet_init(jax.random.PRNGKey(0), config)
        logits = resnet_apply(params, rand(1, 4, 32, 32, 3), config)
        assert logits.shape == (4, 10)
        assert np.isfinite(np.asarray(logits)).all()

    def test_transformer_forward(self):
        config = TransformerConfig(
            vocab_size=128, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jnp.zeros((2, 16), jnp.int32)
        logits = transformer_apply(params, tokens, config)
        assert logits.shape == (2, 16, 128)
        assert np.isfinite(np.asarray(logits)).all()


class TestShardedTraining:
    def test_transformer_dp_tp_training(self):
        mesh = make_mesh(MeshSpec(dp=2, tp=2, sp=2))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=32, dtype=jnp.float32, attention="reference",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        rules = transformer_sharding_rules()
        init_state, train_step = make_train_step(
            lambda p, x: transformer_apply(p, x, config),
            mesh=mesh,
            param_rules=rules,
        )
        state = init_state(params)
        # embed sharded over tp
        embed_sharding = state.params["embed"].sharding
        assert embed_sharding.spec == P("tp", None)

        tokens = jax.device_put(
            jnp.ones((4, 16), jnp.int32),
            batch_sharding(mesh, ndim=2),
        )
        targets = jax.device_put(
            jnp.ones((4, 16), jnp.int32),
            batch_sharding(mesh, ndim=2),
        )
        losses = []
        for _ in range(3):
            state, loss = train_step(state, tokens, targets)
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        assert int(state.step) == 3

    def test_fsdp_rules_match_replicated_training(self):
        """Zero-style parameter sharding (transformer_fsdp_rules): params
        AND optimizer moments shard over dp, and the training trajectory
        is numerically the computation the replicated rules run."""
        from kubeshare_tpu.models.transformer import transformer_fsdp_rules

        mesh = make_mesh(MeshSpec(dp=2, tp=2, sp=2))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=32, dtype=jnp.float32, attention="reference",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64),
            batch_sharding(mesh, ndim=2))

        losses = {}
        for name, rules in (("base", transformer_sharding_rules()),
                            ("fsdp", transformer_fsdp_rules())):
            init_state, train_step = make_train_step(
                lambda p, x: transformer_apply(p, x, config),
                mesh=mesh, param_rules=rules, donate_state=False,
            )
            state = init_state(params)
            if name == "fsdp":
                # weights and adam moments actually shard over dp
                assert state.params["embed"].sharding.spec == P("tp", "dp")
                wq = state.params["layers"][0]["attn"]["wq"]
                assert wq.sharding.spec == P("dp", "tp", None)
                moment = state.opt_state[0].mu["layers"][0]["attn"]["wq"]
                assert moment.sharding.spec == P("dp", "tp", None)
            run = []
            for _ in range(2):
                state, loss = train_step(state, tokens, tokens)
                run.append(float(loss))
            losses[name] = run
        np.testing.assert_allclose(losses["fsdp"], losses["base"],
                                   rtol=2e-5, atol=2e-6)

    def test_mesh_spec_resolution(self):
        assert MeshSpec(dp=-1, tp=2, sp=2).resolve(8) == (2, 1, 2, 2)
        assert MeshSpec(dp=8, tp=1, sp=1).resolve(8) == (8, 1, 1, 1)
        assert MeshSpec(dp=-1, ep=2, tp=2).resolve(8) == (2, 2, 2, 1)
        with pytest.raises(ValueError):
            MeshSpec(dp=3, tp=1, sp=1).resolve(8)

    def test_mesh_axes_with_and_without_ep(self):
        # ep == 1 keeps the historical three-axis shape (sharding rules
        # that name only dp/tp/sp keep working unchanged)
        assert make_mesh(MeshSpec(dp=2, tp=2, sp=2)).axis_names == (
            "dp", "tp", "sp")
        mesh = make_mesh(MeshSpec(dp=2, ep=2, tp=2))
        assert mesh.axis_names == ("dp", "ep", "tp", "sp")
        assert mesh.shape["ep"] == 2
        # batch axis spans dp x ep so every device holds a batch shard
        assert batch_sharding(mesh).spec == P(("dp", "ep"), None)

    def test_shard_params_rules(self):
        mesh = make_mesh(MeshSpec(dp=2, tp=2, sp=2))
        params = {"attn": {"wq": jnp.ones((8, 4, 2))}, "norm": jnp.ones((4,))}
        placed = shard_params(params, {"wq": P(None, "tp", None)}, mesh)
        assert placed["attn"]["wq"].sharding.spec == P(None, "tp", None)
        assert placed["norm"].sharding.spec == P()


class TestRingTransformer:
    def test_ring_forward_matches_dense(self):
        from kubeshare_tpu.models.transformer import transformer_apply_ring

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
        dense = transformer_apply(params, tokens, config)
        ring = transformer_apply_ring(params, tokens, config, mesh)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                                   rtol=2e-4, atol=2e-4)

    def test_windowed_ring_forward_matches_dense(self):
        """A sliding-window model through the sequence-parallel ring must
        match its own dense forward (the band the dense mask keeps)."""
        from kubeshare_tpu.models.transformer import transformer_apply_ring

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
            attention_window=6,
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
        dense = transformer_apply(params, tokens, config)
        ring = transformer_apply_ring(params, tokens, config, mesh)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                                   rtol=2e-4, atol=2e-4)

    def test_gqa_ring_forward_matches_dense(self):
        """A GQA model (2 KV heads under 4 query heads) through the
        sequence-parallel ring must match its own dense forward — the
        model-level closure of the op-level GQA ring tests."""
        from kubeshare_tpu.models.transformer import transformer_apply_ring

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
            d_ff=64, max_seq_len=64, dtype=jnp.float32,
            attention="reference", positional="rope",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
        dense = transformer_apply(params, tokens, config)
        ring = transformer_apply_ring(params, tokens, config, mesh)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                                   rtol=2e-4, atol=2e-4)

    def test_ring_flash_forward_matches_dense(self):
        """Model-level: the Pallas-fused ring body (interpret mode) must
        reproduce the dense forward bit-for-tolerance."""
        from kubeshare_tpu.models.transformer import transformer_apply_ring

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
        dense = transformer_apply(params, tokens, config)
        ring = transformer_apply_ring(params, tokens, config, mesh,
                                      use_flash=True, interpret=True)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("positional", ["rope", "learned"])
    def test_zigzag_ring_forward_matches_dense(self, positional):
        """End-to-end zigzag: tokens permuted once, every layer attends
        with the balanced ring and positions follow the permutation
        (RoPE and the learned table), logits permuted back."""
        from kubeshare_tpu.models.transformer import transformer_apply_ring

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
            positional=positional,
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
        dense = transformer_apply(params, tokens, config)
        ring = transformer_apply_ring(params, tokens, config, mesh,
                                      layout="zigzag", use_flash=False)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                                   rtol=2e-4, atol=2e-4)

    def test_zigzag_ring_flash_forward_matches_dense(self):
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
        ring = transformer_apply_ring(params, tokens, config, mesh,
                                      layout="zigzag", use_flash=True,
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                                   rtol=2e-4, atol=2e-4)

    def test_ring_config_on_dense_entry_raises(self):
        config = TransformerConfig(attention="ring")
        params_cfg = TransformerConfig(
            vocab_size=8, d_model=8, n_heads=2, n_layers=1, d_ff=8,
            max_seq_len=8, dtype=jnp.float32, attention="ring",
        )
        params = transformer_init(jax.random.PRNGKey(0), params_cfg)
        with pytest.raises(ValueError):
            transformer_apply(params, jnp.zeros((1, 8), jnp.int32), params_cfg)


class TestUlyssesTransformer:
    def test_forward_matches_dense(self):
        from kubeshare_tpu.models.transformer import transformer_apply_ulysses

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
        dense = transformer_apply(params, tokens, config)
        out = transformer_apply_ulysses(params, tokens, config, mesh)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(out),
                                   rtol=2e-4, atol=2e-4)

    def test_windowed_forward_matches_dense(self):
        from kubeshare_tpu.models.transformer import transformer_apply_ulysses

        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
            attention_window=8,
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
        dense = transformer_apply(params, tokens, config)
        out = transformer_apply_ulysses(params, tokens, config, mesh)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(out),
                                   rtol=2e-4, atol=2e-4)

    def test_indivisible_heads_raises(self):
        from kubeshare_tpu.models.transformer import transformer_apply_ulysses

        mesh = make_mesh(MeshSpec(dp=1, tp=1, sp=8))
        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
        )
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jnp.zeros((1, 32), jnp.int32)
        with pytest.raises(ValueError, match="divisible"):
            transformer_apply_ulysses(params, tokens, config, mesh)

    def test_ulysses_config_on_dense_entry_raises(self):
        cfg = TransformerConfig(
            vocab_size=8, d_model=8, n_heads=2, n_layers=1, d_ff=8,
            max_seq_len=8, dtype=jnp.float32, attention="ulysses",
        )
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError):
            transformer_apply(params, jnp.zeros((1, 8), jnp.int32), cfg)


class TestRemat:
    def test_remat_grads_match(self):
        base = dict(vocab_size=32, d_model=16, n_heads=2, n_layers=2,
                    d_ff=32, max_seq_len=16, dtype=jnp.float32,
                    attention="reference")
        plain = TransformerConfig(**base)
        remat = TransformerConfig(**base, remat=True)
        params = transformer_init(jax.random.PRNGKey(0), plain)
        tokens = jnp.ones((2, 8), jnp.int32)

        def loss(config):
            return lambda p: (transformer_apply(p, tokens, config) ** 2).mean()

        g_plain = jax.grad(loss(plain))(params)
        g_remat = jax.grad(loss(remat))(params)
        for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_remat)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
