"""Expert parallelism (MoE) and pipeline parallelism tests on the CPU mesh.

The transformer on these schedules is in files of its own because a tier-1
worker holds a file for its whole length (``--dist loadfile``):
``tests/test_parallel_pipelined.py``, ``test_parallel_pp_sp.py`` and
``test_parallel_moe_sp.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeshare_tpu.ops.moe import MoEConfig, moe_apply, moe_init, moe_sharding_rules
from kubeshare_tpu.parallel import MeshSpec, make_mesh
from kubeshare_tpu.parallel.mesh import shard_params
from kubeshare_tpu.parallel.pipeline import pipeline_apply, stack_stage_params


class TestMoE:
    def test_forward_shapes_and_aux(self):
        config = MoEConfig(d_model=16, d_ff=32, num_experts=4, capacity_factor=2.0)
        params = moe_init(jax.random.PRNGKey(0), config)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
        out, aux = moe_apply(params, x, config)
        assert out.shape == x.shape
        assert np.isfinite(np.asarray(out)).all()
        # balanced-ish routing on random data: aux near 1.0
        assert 0.5 < float(aux) < 4.0

    def test_capacity_drops_tokens(self):
        # capacity so small that most tokens are dropped -> output mostly 0
        config = MoEConfig(d_model=8, d_ff=8, num_experts=2, capacity_factor=0.1)
        params = moe_init(jax.random.PRNGKey(0), config)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 8))
        out, _ = moe_apply(params, x, config)
        zero_rows = np.sum(np.all(np.asarray(out[0]) == 0.0, axis=-1))
        assert zero_rows >= 28  # capacity 1 per expert -> at most ~4 kept

    @staticmethod
    def _dense_reference(params, x, k):
        """Route through EVERY expert densely, then keep the top-k mixture —
        the semantics moe_apply's capacity-bounded dispatch must reproduce
        when nothing is dropped."""
        n = x.shape[0] * x.shape[1]
        d = x.shape[-1]
        tokens = x.reshape(n, d)
        probs = jax.nn.softmax(tokens @ params["router"], axis=-1)
        gate, idx = jax.lax.top_k(probs, k)
        if k > 1:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        hidden = jax.nn.gelu(jnp.einsum("nd,edf->enf", tokens, params["w_in"]))
        outs = jnp.einsum("enf,efd->end", hidden, params["w_out"])  # [e, n, d]
        out = sum(
            gate[:, j, None] * outs[idx[:, j], jnp.arange(n)] for j in range(k)
        )
        return out.reshape(x.shape)

    @pytest.mark.parametrize("top_k", [1, 2, 3])
    def test_topk_matches_dense_reference_at_full_capacity(self, top_k):
        config = MoEConfig(d_model=16, d_ff=32, num_experts=4, top_k=top_k)
        params = moe_init(jax.random.PRNGKey(0), config)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
        out, _ = moe_apply(params, x, config, capacity=16)
        expected = self._dense_reference(params, x, top_k)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=1e-5, atol=1e-5)

    def test_top2_grads_reach_every_expert(self):
        # with E=2 and top_k=2 every token touches both experts, so both
        # experts' weights must receive gradient
        config = MoEConfig(d_model=8, d_ff=16, num_experts=2, top_k=2,
                           capacity_factor=2.0)
        params = moe_init(jax.random.PRNGKey(0), config)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8))

        grads = jax.grad(
            lambda p: jnp.mean(moe_apply(p, x, config)[0] ** 2)
        )(params)
        g_in = np.asarray(grads["w_in"])
        assert (np.abs(g_in).sum(axis=(1, 2)) > 0).all()

    def test_top2_overflow_drops_second_choices_first(self):
        # a router hard-biased so every token's first choice is expert 0 and
        # second choice expert 1: with capacity exactly n, expert 0 keeps
        # every first choice and the aux-capacity accounting never lets a
        # second choice evict one
        config = MoEConfig(d_model=4, d_ff=8, num_experts=2, top_k=2)
        params = dict(moe_init(jax.random.PRNGKey(0), config))
        params["router"] = jnp.array([[4.0, 2.0]] * 4)  # e0 always wins
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 6, 4))) + 0.1
        out_full, _ = moe_apply(params, x, config, capacity=6)
        # capacity 6 fits all 6 first choices AND all 6 second choices
        expected = self._dense_reference(params, x, 2)
        np.testing.assert_allclose(np.asarray(out_full),
                                   np.asarray(expected), rtol=1e-5, atol=1e-5)
        # capacity 3: half of each expert's buffer — first choices beyond 3
        # drop, but no kept token's gate is reweighted
        out_small, _ = moe_apply(params, x, config, capacity=3)
        kept_rows = np.any(np.asarray(out_small[0]) != 0.0, axis=-1)
        assert kept_rows.sum() >= 3

    def test_derived_capacity_includes_k(self):
        # top_k=2, E=2, n=8, cf=1.0 -> capacity ceil(1.0*2*8/2)=8: nothing
        # drops even when routing is maximally unbalanced per choice rank
        config = MoEConfig(d_model=4, d_ff=8, num_experts=2,
                           capacity_factor=1.0, top_k=2)
        params = dict(moe_init(jax.random.PRNGKey(0), config))
        params["router"] = jnp.array([[4.0, 2.0]] * 4)
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 8, 4))) + 0.1
        out, _ = moe_apply(params, x, config)
        expected = self._dense_reference(params, x, 2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("top_k", [1, 2])
    @pytest.mark.parametrize("routing", ["tokens_choose", "experts_choose"])
    def test_scatter_dispatch_matches_einsum(self, top_k, routing):
        """The permutation (scatter/gather) dispatch is the same math as
        the dense one-hot einsums — including under capacity overflow,
        where both must drop the same weakest choices (VERDICT r3 #4)."""
        for capacity in (None, 3):  # derived (no drops) and overflowing
            kwargs = dict(d_model=16, d_ff=32, num_experts=4, top_k=top_k,
                          routing=routing, capacity_factor=1.5)
            params = moe_init(jax.random.PRNGKey(0), MoEConfig(**kwargs))
            x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
            out_s, aux_s = moe_apply(
                params, x, MoEConfig(dispatch="scatter", **kwargs),
                capacity=capacity)
            out_e, aux_e = moe_apply(
                params, x, MoEConfig(dispatch="einsum", **kwargs),
                capacity=capacity)
            np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_e),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(float(aux_s), float(aux_e), rtol=1e-6)

    def test_scatter_dispatch_grads_match_einsum(self):
        config_kwargs = dict(d_model=8, d_ff=16, num_experts=4, top_k=2,
                             capacity_factor=1.25)
        params = moe_init(jax.random.PRNGKey(0), MoEConfig(**config_kwargs))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 8))

        def loss(p, dispatch):
            out, aux = moe_apply(
                p, x, MoEConfig(dispatch=dispatch, **config_kwargs))
            return jnp.mean(out ** 2) + 0.01 * aux

        g_s = jax.jit(jax.grad(lambda p: loss(p, "scatter")))(params)
        g_e = jax.jit(jax.grad(lambda p: loss(p, "einsum")))(params)
        for name in ("router", "w_in", "w_out"):
            np.testing.assert_allclose(np.asarray(g_s[name]),
                                       np.asarray(g_e[name]),
                                       rtol=1e-4, atol=1e-6)

    def test_unknown_dispatch_rejected(self):
        config = MoEConfig(d_model=4, d_ff=8, num_experts=2, dispatch="bogus")
        params = moe_init(jax.random.PRNGKey(0), config)
        with pytest.raises(ValueError, match="dispatch"):
            moe_apply(params, jnp.zeros((1, 2, 4)), config)

    @pytest.mark.parametrize("bad_k", [0, -1, 5])
    def test_top_k_validated(self, bad_k):
        config = MoEConfig(d_model=4, d_ff=8, num_experts=4, top_k=bad_k)
        params = moe_init(jax.random.PRNGKey(0), config)
        x = jnp.zeros((1, 2, 4))
        with pytest.raises(ValueError, match="top_k"):
            moe_apply(params, x, config)

    def test_experts_choose_full_capacity_is_soft_mixture(self):
        """Expert-choice at capacity=n: every expert picks every token
        (gated by its affinity), so the output equals the dense softmax-
        weighted mixture over ALL experts — a closed-form reference."""
        config = MoEConfig(d_model=16, d_ff=32, num_experts=4,
                           routing="experts_choose")
        params = moe_init(jax.random.PRNGKey(0), config)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
        out, aux = moe_apply(params, x, config, capacity=16)
        assert float(aux) == 0.0  # balanced by construction: no aux loss

        tokens = x.reshape(16, 16)
        probs = jax.nn.softmax(tokens @ params["router"], axis=-1)
        hidden = jax.nn.gelu(
            jnp.einsum("nd,edf->enf", tokens, params["w_in"]))
        outs = jnp.einsum("enf,efd->end", hidden, params["w_out"])
        expected = jnp.einsum("ne,end->nd", probs, outs).reshape(x.shape)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=1e-5, atol=1e-5)

    def test_experts_choose_load_balanced_by_construction(self):
        # capacity 2 with 2 experts: at most 4 token-slots filled, and no
        # expert ever exceeds its capacity regardless of router skew
        config = MoEConfig(d_model=8, d_ff=16, num_experts=2,
                           routing="experts_choose")
        params = dict(moe_init(jax.random.PRNGKey(0), config))
        params["router"] = jnp.array([[5.0, -5.0]] * 8)  # heavy skew
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8))) + 0.1
        out, _ = moe_apply(params, x, config, capacity=2)
        touched = np.any(np.asarray(out[0]) != 0.0, axis=-1)
        assert 2 <= touched.sum() <= 4

    def test_experts_choose_grads_reach_every_expert(self):
        config = MoEConfig(d_model=8, d_ff=16, num_experts=4,
                           capacity_factor=2.0, routing="experts_choose")
        params = moe_init(jax.random.PRNGKey(0), config)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8))
        grads = jax.grad(
            lambda p: jnp.mean(moe_apply(p, x, config)[0] ** 2)
        )(params)
        g_in = np.asarray(grads["w_in"])
        assert (np.abs(g_in).sum(axis=(1, 2)) > 0).all()

    def test_unknown_routing_rejected(self):
        config = MoEConfig(d_model=8, d_ff=16, num_experts=2,
                           routing="coin_flip")
        params = moe_init(jax.random.PRNGKey(0), config)
        with pytest.raises(ValueError, match="routing"):
            moe_apply(params, jnp.zeros((1, 2, 8)), config)

    def test_expert_parallel_training(self):
        mesh = make_mesh(MeshSpec(dp=4, tp=2, sp=1))
        config = MoEConfig(d_model=16, d_ff=32, num_experts=4)
        params = moe_init(jax.random.PRNGKey(0), config)
        params = shard_params(params, moe_sharding_rules(ep_axis="dp"), mesh)
        assert params["w_in"].sharding.spec == P("dp", None, None)

        x = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(1), (8, 4, 16)),
            NamedSharding(mesh, P("dp", None, None)),
        )

        @jax.jit
        def loss_fn(params, x):
            out, aux = moe_apply(params, x, config)
            return jnp.mean(out**2) + 0.01 * aux

        loss, grads = jax.value_and_grad(loss_fn)(params, x)
        assert np.isfinite(float(loss))
        assert np.isfinite(np.asarray(grads["router"])).all()

    def test_dedicated_ep_axis_matches_unsharded(self):
        """Experts over their own mesh axis (dp x ep composition, the
        GShard layout): batch sharded over (dp, ep), experts over ep only
        — forward and grads must equal the single-device computation."""
        from kubeshare_tpu.parallel import batch_sharding

        mesh = make_mesh(MeshSpec(dp=2, ep=2, tp=2))
        config = MoEConfig(d_model=16, d_ff=32, num_experts=4, top_k=2,
                           capacity_factor=8.0)
        params = moe_init(jax.random.PRNGKey(0), config)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 16))

        def loss_fn(params, x):
            out, aux = moe_apply(params, x, config)
            return jnp.mean(out**2) + 0.01 * aux

        base_loss, base_grads = jax.value_and_grad(loss_fn)(params, x)

        placed = shard_params(params, moe_sharding_rules(ep_axis="ep"), mesh)
        assert placed["w_in"].sharding.spec == P("ep", None, None)
        x_sharded = jax.device_put(x, batch_sharding(mesh, ndim=3))
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(placed, x_sharded)

        np.testing.assert_allclose(float(loss), float(base_loss),
                                   rtol=1e-5, atol=1e-6)
        for key in ("router", "w_in", "w_out"):
            np.testing.assert_allclose(
                np.asarray(grads[key]), np.asarray(base_grads[key]),
                rtol=2e-4, atol=1e-5)


class TestPipeline:
    def test_matches_sequential(self):
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("pp",))
        n_stages = 4

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        keys = jax.random.split(jax.random.PRNGKey(0), n_stages)
        per_stage = [jax.random.normal(k, (8, 8)) * 0.5 for k in keys]
        stacked = stack_stage_params(per_stage)

        x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
        expected = x
        for w in per_stage:
            expected = stage_fn(w, expected)

        out = pipeline_apply(stacked, x, stage_fn, mesh,
                             num_microbatches=4, pp_axis="pp")
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=1e-5, atol=1e-5)

    def test_grads_flow_through_pipeline(self):
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("pp",))

        def stage_fn(w, x):
            return jax.nn.relu(x @ w)

        per_stage = [jax.random.normal(jax.random.PRNGKey(i), (4, 4)) * 0.5
                     for i in range(2)]
        stacked = stack_stage_params(per_stage)
        x = jax.random.normal(jax.random.PRNGKey(9), (4, 4))

        def loss(params):
            return pipeline_apply(params, x, stage_fn, mesh,
                                  num_microbatches=2).sum()

        grads = jax.jit(jax.grad(loss))(stacked)
        assert np.isfinite(np.asarray(grads)).all()
        assert np.abs(np.asarray(grads)).sum() > 0


class TestPipeline1F1B:
    """1F1B schedule (VERDICT r1 #6): gradient equivalence vs GPipe-autodiff
    and O(stages) activation stash instead of O(microbatches)."""

    def _setup(self, n_stages=4, num_microbatches=8, d=8, batch=16):
        from kubeshare_tpu.parallel.pipeline import pipeline_train_1f1b

        mesh = Mesh(np.array(jax.devices()[:n_stages]).reshape(n_stages),
                    ("pp",))

        def stage_fn(params, x):
            return jnp.tanh(x @ params["w"] + params["b"])

        per_stage = [
            {
                "w": jax.random.normal(jax.random.PRNGKey(i), (d, d)) * 0.5,
                "b": jnp.zeros((d,)) + 0.01 * i,
            }
            for i in range(n_stages)
        ]
        stacked = stack_stage_params(per_stage)
        x = jax.random.normal(jax.random.PRNGKey(50), (batch, d))
        y = jax.random.normal(jax.random.PRNGKey(51), (batch, d))

        def loss_fn(out, target):
            return ((out - target) ** 2).mean()

        return pipeline_train_1f1b, mesh, stage_fn, stacked, x, y, loss_fn

    def test_loss_and_grads_match_gpipe(self):
        (train_1f1b, mesh, stage_fn, stacked, x, y,
         loss_fn) = self._setup()
        M = 8

        loss_1f1b, grads_1f1b = train_1f1b(
            stacked, x, y, stage_fn, loss_fn, mesh, num_microbatches=M
        )

        def gpipe_loss(params):
            out = pipeline_apply(params, x, stage_fn, mesh, num_microbatches=M)
            micro_out = out.reshape(M, -1, out.shape[-1])
            micro_y = y.reshape(M, -1, y.shape[-1])
            return jax.vmap(loss_fn)(micro_out, micro_y).mean()

        loss_ref, grads_ref = jax.jit(jax.value_and_grad(gpipe_loss))(stacked)
        np.testing.assert_allclose(float(loss_1f1b), float(loss_ref),
                                   rtol=1e-5, atol=1e-6)
        for key in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(grads_1f1b[key]), np.asarray(grads_ref[key]),
                rtol=1e-4, atol=1e-5,
            )

    def test_two_stage_many_microbatches(self):
        (train_1f1b, _, stage_fn, _, _, _, loss_fn) = self._setup()
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("pp",))
        d, M = 4, 16  # microbatches >> stages: the stash must still be tiny
        per_stage = [
            {"w": jax.random.normal(jax.random.PRNGKey(i), (d, d)) * 0.5,
             "b": jnp.zeros((d,))}
            for i in range(2)
        ]
        stacked = stack_stage_params(per_stage)
        x = jax.random.normal(jax.random.PRNGKey(3), (32, d))
        y = jax.random.normal(jax.random.PRNGKey(4), (32, d))
        from kubeshare_tpu.parallel.pipeline import pipeline_train_1f1b

        loss, grads = pipeline_train_1f1b(
            stacked, x, y, stage_fn, loss_fn, mesh, num_microbatches=M
        )

        def gpipe_loss(params):
            out = pipeline_apply(params, x, stage_fn, mesh, num_microbatches=M)
            micro_out = out.reshape(M, -1, d)
            micro_y = y.reshape(M, -1, d)
            return jax.vmap(loss_fn)(micro_out, micro_y).mean()

        loss_ref, grads_ref = jax.jit(jax.value_and_grad(gpipe_loss))(stacked)
        np.testing.assert_allclose(float(loss), float(loss_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(grads["w"]),
                                   np.asarray(grads_ref["w"]),
                                   rtol=1e-4, atol=1e-5)

    def test_activation_memory_is_o_stages(self):
        """The compiled 1F1B program's activation stash is the static ring
        of min(M, 2S-1) slots — grow M 4x and the live-buffer footprint
        must stay ~flat (GPipe-autodiff grows linearly)."""
        from kubeshare_tpu.parallel.pipeline import pipeline_train_1f1b

        n_stages, d = 2, 8
        mesh = Mesh(np.array(jax.devices()[:n_stages]).reshape(n_stages),
                    ("pp",))

        def stage_fn(params, x):
            return jnp.tanh(x @ params["w"])

        per_stage = [{"w": jnp.eye(d) * 0.5} for _ in range(n_stages)]
        stacked = stack_stage_params(per_stage)

        def loss_fn(out, target):
            return ((out - target) ** 2).mean()

        def peak_temp(M, batch):
            x = jnp.zeros((batch, d))
            y = jnp.zeros((batch, d))
            compiled = (
                jax.jit(
                    lambda p: pipeline_train_1f1b(
                        p, x, y, stage_fn, loss_fn, mesh, num_microbatches=M
                    )
                )
                .lower(stacked)
                .compile()
            )
            analysis = compiled.memory_analysis()
            if analysis is None:
                pytest.skip("backend exposes no memory analysis")
            return analysis.temp_size_in_bytes

        # microbatch size held constant (8): batch scales with M
        small = peak_temp(M=4, batch=32)
        large = peak_temp(M=16, batch=128)
        # GPipe-autodiff would stash 4x the activations; the 1F1B ring is
        # the same static size both times.  Allow 2x slack for XLA temps
        # that legitimately scale with total batch (I/O staging etc.).
        assert large <= 2 * max(small, 1), (small, large)
