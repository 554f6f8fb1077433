"""Compute-path tests: the capacity-factor MoE transformer (routing, aux
loss, decode parity, capacity)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from kubeshare_tpu.models import (
    TransformerConfig,
    transformer_apply,
    transformer_apply_with_aux,
    transformer_init,
)
from kubeshare_tpu.models.transformer import transformer_sharding_rules
from kubeshare_tpu.parallel import MeshSpec, make_mesh
from kubeshare_tpu.parallel.mesh import shard_params
from kubeshare_tpu.parallel.train import cross_entropy_loss


class TestMoEFlagship:
    """MoE layers inside the flagship Transformer (config.moe_every)."""

    def _config(self, **kw):
        kw.setdefault("moe_every", 2)
        kw.setdefault("moe_num_experts", 4)
        kw.setdefault("moe_capacity_factor", 8.0)  # ample: no token drops
        kw.setdefault("attention", "reference")
        return TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
            max_seq_len=32, dtype=jnp.float32, **kw)

    def test_init_places_moe_layers(self):
        config = self._config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        kinds = ["moe" if "moe" in l else "mlp" for l in params["layers"]]
        assert kinds == ["mlp", "moe", "mlp", "moe"]
        assert params["layers"][1]["moe"]["w_in"].shape == (4, 32, 64)

    def test_forward_and_aux(self):
        config = self._config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
        logits = transformer_apply(params, tokens, config)
        assert logits.shape == (2, 16, 64)
        assert np.isfinite(np.asarray(logits)).all()
        logits2, aux = transformer_apply_with_aux(params, tokens, config)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits2))
        assert float(aux) > 0.0  # two MoE layers contribute load-balance loss

    def test_router_gets_gradients_through_aux(self):
        config = self._config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 64)

        def loss(p):
            logits, aux = transformer_apply_with_aux(p, tokens, config)
            targets = jnp.zeros(tokens.shape, jnp.int32)
            return cross_entropy_loss(logits, targets) + 0.01 * aux

        grads = jax.jit(jax.grad(loss))(params)
        g_router = np.asarray(grads["layers"][1]["moe"]["router"])
        assert np.isfinite(g_router).all()
        assert np.abs(g_router).sum() > 0

    def test_decode_matches_full_forward(self):
        from kubeshare_tpu.models.decoding import (
            prefill_incremental as prefill)

        config = self._config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 10), 0, 64)
        dense = transformer_apply(params, prompt, config)
        _, last_logits = prefill(params, config, prompt)
        np.testing.assert_allclose(
            np.asarray(dense[:, -1]), np.asarray(last_logits),
            rtol=2e-4, atol=2e-4)

    def test_sampled_decode_runs(self):
        from kubeshare_tpu.models.decoding import sample_decode

        config = self._config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = jnp.zeros((1, 4), jnp.int32)
        toks = sample_decode(params, config, prompt, jax.random.PRNGKey(5),
                             6, temperature=0.8, top_k=8)
        assert toks.shape == (1, 6)

    def test_sharding_rules_place_experts_on_tp(self):
        from kubeshare_tpu.models.transformer import transformer_sharding_rules
        from kubeshare_tpu.parallel.mesh import shard_params

        config = self._config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        mesh = make_mesh(MeshSpec(dp=2, tp=2, sp=2))
        placed = shard_params(params, transformer_sharding_rules(), mesh)
        moe = placed["layers"][1]["moe"]
        assert moe["w_in"].sharding.spec == P("tp", None, None)
        assert moe["w_out"].sharding.spec == P("tp", None, None)
        assert moe["router"].sharding.spec == P()
        # tp-sharded forward still matches unsharded
        tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0, 64)
        base = transformer_apply(params, tokens, config)
        sharded = jax.jit(
            lambda p, t: transformer_apply(p, t, config))(placed, tokens)
        np.testing.assert_allclose(np.asarray(base), np.asarray(sharded),
                                   rtol=2e-4, atol=2e-4)

    def test_sp_entries_accept_token_choice_moe(self):
        """Round 4: the standalone sp entries route MoE per shard
        (TestMoESequenceParallel locks dense equivalence); only
        expert-choice routing — whole-batch by construction — is
        rejected there."""
        from dataclasses import replace

        from kubeshare_tpu.models.transformer import transformer_apply_ring

        config = self._config(attention="ring")
        params = transformer_init(jax.random.PRNGKey(0), self._config())
        mesh = make_mesh(MeshSpec(dp=2, tp=1, sp=4))
        out = transformer_apply_ring(params, jnp.zeros((2, 8), jnp.int32),
                                     config, mesh)
        assert np.isfinite(np.asarray(out)).all()
        ec = replace(config, moe_routing="experts_choose")
        with pytest.raises(ValueError, match="whole-batch"):
            transformer_apply_ring(params, jnp.zeros((2, 8), jnp.int32),
                                   ec, mesh)

    @pytest.mark.parametrize("attention", ["reference", "ring"])
    def test_pipelined_paths_reject_moe(self, attention):
        """Both pipelined branches (dense AND sp-in-stage) must refuse MoE
        configs — the stage body would otherwise silently run MoE layers
        with default routing hyperparameters and drop the aux loss."""
        from jax.sharding import Mesh
        from kubeshare_tpu.models.transformer import (
            transformer_apply_pipelined, transformer_train_1f1b)

        config = self._config(attention=attention, moe_every=1,
                              positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), self._config())
        shape = (2, 2) if attention == "ring" else (2,)
        axes = ("pp", "sp") if attention == "ring" else ("pp",)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(*shape)
                    if attention == "ring"
                    else np.array(jax.devices()[:2]).reshape(2), axes)
        tokens = jnp.zeros((2, 8), jnp.int32)
        with pytest.raises(ValueError, match="MoE"):
            transformer_apply_pipelined(params, tokens, config, mesh)
        with pytest.raises(ValueError, match="MoE"):
            transformer_train_1f1b(params, tokens, tokens, config, mesh)

    def test_top2_forward_grads_and_decode_parity(self):
        """The flagship wired for GShard-style top-2 (config.moe_top_k=2):
        forward + grads finite, and incremental decode matches the dense
        forward — the dispatch/combine paths must agree for k>1 too."""
        from kubeshare_tpu.models.decoding import prefill

        config = self._config(moe_top_k=2)
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 12), 0, 64)
        logits, aux = transformer_apply_with_aux(params, tokens, config)
        assert np.isfinite(np.asarray(logits)).all()
        assert float(aux) > 0.0

        def loss(p):
            lg, ax = transformer_apply_with_aux(p, tokens, config)
            return cross_entropy_loss(lg, jnp.zeros_like(tokens)) + 0.01 * ax

        grads = jax.jit(jax.grad(loss))(params)
        for li in (1, 3):
            g = np.asarray(grads["layers"][li]["moe"]["w_in"])
            assert np.isfinite(g).all() and np.abs(g).sum() > 0

        dense = transformer_apply(params, tokens, config)
        _, last_logits = prefill(params, config, tokens)
        np.testing.assert_allclose(
            np.asarray(dense[:, -1]), np.asarray(last_logits),
            rtol=2e-4, atol=2e-4)

    def test_experts_choose_flagship_trains_but_refuses_decode(self):
        """moe_routing='experts_choose': training works (grads finite,
        zero aux), incremental decode raises — expert choices depend on
        the whole sequence and cannot be replayed token-by-token."""
        from kubeshare_tpu.models.decoding import prefill

        config = self._config(moe_routing="experts_choose",
                              moe_capacity_factor=2.0)
        params = transformer_init(jax.random.PRNGKey(0), config)
        tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 12), 0, 64)
        logits, aux = transformer_apply_with_aux(params, tokens, config)
        assert np.isfinite(np.asarray(logits)).all()
        assert float(aux) == 0.0

        grads = jax.grad(lambda p: cross_entropy_loss(
            transformer_apply(p, tokens, config), tokens))(params)
        g = np.asarray(grads["layers"][1]["moe"]["w_in"])
        assert np.isfinite(g).all() and np.abs(g).sum() > 0

        with pytest.raises(ValueError, match="expert-choice"):
            prefill(params, config, tokens)

    def test_decode_batch_independent_at_default_capacity(self):
        """Batched incremental decode must equal per-row decode even at the
        default capacity_factor (1.25): the decode path pins capacity to the
        per-step token count, so expert collisions between batch rows can
        never drop a row's token (ADVICE r2, decoding.py)."""
        from kubeshare_tpu.models.decoding import prefill

        config = self._config(moe_capacity_factor=1.25)
        params = transformer_init(jax.random.PRNGKey(0), config)
        # batch 4 over 4 experts: some step almost surely routes two rows
        # to the same expert, which the old factor-derived capacity dropped
        prompt = jax.random.randint(jax.random.PRNGKey(7), (4, 8), 0, 64)
        _, batched = prefill(params, config, prompt)
        for row in range(prompt.shape[0]):
            _, single = prefill(params, config, prompt[row:row + 1])
            np.testing.assert_allclose(
                np.asarray(batched[row:row + 1]), np.asarray(single),
                rtol=2e-4, atol=2e-4)


class TestMoECapacity:
    def test_capacity_rounds_up(self):
        """capacity = ceil(cf*n/e), not floor (ADVICE r2, moe.py): route all
        5 tokens to expert 0 with cf=1.0, e=4 -> capacity must be 2, so
        exactly 2 token rows survive (floor kept only 1)."""
        from kubeshare_tpu.ops.moe import MoEConfig, moe_apply, moe_init

        config = MoEConfig(d_model=8, d_ff=16, num_experts=4,
                           capacity_factor=1.0)
        params = dict(moe_init(jax.random.PRNGKey(0), config))
        router = np.zeros((8, 4), np.float32)
        router[:, 0] = 100.0  # positive-sum tokens all argmax to expert 0
        params["router"] = jnp.asarray(router)
        x = 0.1 + jnp.abs(
            jax.random.normal(jax.random.PRNGKey(1), (1, 5, 8), jnp.float32))
        out, _ = moe_apply(params, x, config)
        kept_rows = np.abs(np.asarray(out[0])).sum(axis=-1) > 0
        assert kept_rows.sum() == 2

    def test_capacity_override_keeps_all_tokens(self):
        from kubeshare_tpu.ops.moe import MoEConfig, moe_apply, moe_init

        config = MoEConfig(d_model=8, d_ff=16, num_experts=4,
                           capacity_factor=1.0)
        params = moe_init(jax.random.PRNGKey(0), config)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 6, 8), jnp.float32)
        ample = moe_apply(params, x, config, capacity=12)[0]
        huge_cf = moe_apply(
            params, x,
            MoEConfig(d_model=8, d_ff=16, num_experts=4,
                      capacity_factor=100.0))[0]
        np.testing.assert_allclose(np.asarray(ample), np.asarray(huge_cf),
                                   rtol=1e-6, atol=1e-6)
