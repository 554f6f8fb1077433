"""Tensor-parallel sharded serving tests (serving/sharded.py).

The contract under test is the ISSUE's acceptance bar: on a forced
multi-device CPU mesh, a sharded engine's streams are BIT-IDENTICAL to
the single-device engine's — greedy and sampled, GQA/windowed/MoE,
through prefix-cache hits, CoW divergence, preemption-resume, tiering
round-trips, and speculation — with zero recompiles after warmup.  Plus
the strict-mesh satellite: ``MeshSpec.resolve`` rejects degenerate
specs loudly and ``serving_mesh`` builds the serving preset.

Workload geometries deliberately mirror tests/test_serving*.py's (same
prompts, same PRNG seeds, same engine shapes) so the single-device
references hit the persistent compile cache instead of compiling anew.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models.transformer import TransformerConfig, transformer_init
from kubeshare_tpu.parallel.mesh import MeshSpec, serving_mesh

pytestmark = pytest.mark.serving

TP = 4
TP_SPEC = MeshSpec(dp=1, tp=TP, sp=1)
needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < TP,
    reason=f"needs {TP} devices (conftest forces 8 CPU devices)")


def _small_config(**extra):
    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32, attention="reference", **extra)


def _sharded_engine(params, config, **overrides):
    from kubeshare_tpu.serving import EngineConfig, ServingEngine

    kwargs = dict(num_slots=3, block_size=4, num_blocks=41,
                  max_request_len=48, prefill_chunk=8, mesh_spec=TP_SPEC)
    kwargs.update(overrides)
    return ServingEngine(params, config, EngineConfig(**kwargs))


def _run_sequentially(engine, reqs):
    from kubeshare_tpu.serving import Request

    out = {}
    for req in reqs:
        engine.submit(Request(**req))
        out.update({rid: r.tokens for rid, r in engine.run().items()
                    if r.done})
        engine.pop_finished()
    return out


class TestServingMeshStrict:
    """Satellite: ``MeshSpec.resolve`` fails loudly on every degenerate
    spec (zero axes, ambiguous fills, wrong products) and the
    ``serving_mesh`` preset builds the dp x tp serving shape."""

    def test_zero_axis_is_loud(self):
        with pytest.raises(ValueError, match="degenerate"):
            MeshSpec(dp=0, tp=1).resolve(4)
        with pytest.raises(ValueError, match="degenerate"):
            MeshSpec(tp=-2).resolve(4)

    def test_multiple_fill_axes_are_ambiguous(self):
        with pytest.raises(ValueError, match="ambiguous"):
            MeshSpec(dp=-1, tp=-1).resolve(8)

    def test_wrong_product_is_loud(self):
        # over-subscribed (the old code silently truncated devices)
        with pytest.raises(ValueError, match="spans 6 devices"):
            MeshSpec(dp=2, tp=3).resolve(4)
        # under-subscribed
        with pytest.raises(ValueError, match="spans 2 devices"):
            MeshSpec(dp=1, tp=2).resolve(8)
        # fill axis that cannot absorb evenly
        with pytest.raises(ValueError, match="multiple of 3"):
            MeshSpec(dp=-1, tp=3).resolve(8)

    def test_valid_specs_resolve(self):
        assert MeshSpec(dp=-1, tp=2).resolve(8) == (4, 1, 2, 1)
        assert MeshSpec(dp=2, tp=2, sp=2).resolve(8) == (2, 1, 2, 2)
        assert MeshSpec(dp=1, tp=1).resolve(1) == (1, 1, 1, 1)

    @needs_mesh
    def test_serving_mesh_preset(self):
        mesh = serving_mesh(TP)
        assert dict(mesh.shape) == {"dp": 1, "tp": TP, "sp": 1}
        # uses the LEADING tp devices, even when more are available
        assert list(mesh.devices.flat) == jax.devices()[:TP]

    def test_serving_mesh_validation_is_loud(self):
        with pytest.raises(ValueError, match="tp >= 1"):
            serving_mesh(0)
        n = len(jax.devices())
        with pytest.raises(ValueError, match=f"only {n} available"):
            serving_mesh(n + 1)


class TestShardingPlan:
    """The tri-state sharding decision: head-sharded when KV heads
    divide tp, replicated-KV fallback when there are fewer KV heads
    than devices, a loud error for indivisible splits — and MoE expert
    weights always replicated (expert psums would break the
    no-partial-sums bit-exactness rule)."""

    def test_head_sharded_when_divisible(self):
        from kubeshare_tpu.serving import plan_sharding

        dec = plan_sharding(_small_config(), TP)
        assert dec.attn_sharded and dec.mlp_sharded and dec.lm_head_sharded

    def test_replicated_fallback_when_kv_heads_below_tp(self):
        from kubeshare_tpu.serving import plan_sharding

        dec = plan_sharding(
            _small_config(n_kv_heads=2, positional="rope"), TP)
        assert not dec.attn_sharded
        assert dec.mlp_sharded  # the MLP halves still shard

    def test_indivisible_kv_heads_is_loud(self):
        from kubeshare_tpu.serving import plan_sharding

        config = TransformerConfig(
            vocab_size=64, d_model=48, n_heads=12, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
            n_kv_heads=6)
        with pytest.raises(ValueError, match="not divisible by tp=4"):
            plan_sharding(config, TP)

    def test_indivisible_d_ff_is_loud(self):
        from kubeshare_tpu.serving import plan_sharding

        config = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=66,
            max_seq_len=64, dtype=jnp.float32, attention="reference")
        with pytest.raises(ValueError, match="d_ff 66"):
            plan_sharding(config, TP)

    def test_indivisible_vocab_falls_back_to_replicated_lm_head(self):
        from kubeshare_tpu.serving import plan_sharding

        config = TransformerConfig(
            vocab_size=63, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference")
        dec = plan_sharding(config, TP)
        assert dec.attn_sharded and not dec.lm_head_sharded

    def test_moe_experts_stay_replicated(self):
        from jax.sharding import PartitionSpec as P

        from kubeshare_tpu.parallel.mesh import param_spec_tree
        from kubeshare_tpu.serving import (plan_sharding,
                                           serving_sharding_rules)

        config = _small_config(moe_every=2, moe_num_experts=4, moe_top_k=2)
        params = transformer_init(jax.random.PRNGKey(0), config)
        rules = serving_sharding_rules(plan_sharding(config, TP))
        specs = param_spec_tree(params, rules)
        # layer 0 is dense: its MLP shards; layer 1 is MoE: replicated
        assert specs["layers"][0]["mlp"]["w_in"] == P(None, "tp")
        assert specs["layers"][1]["moe"]["w_in"] == P()
        assert specs["layers"][1]["moe"]["w_out"] == P()


@needs_mesh
class TestShardedServing:
    """The acceptance suite: sharded streams bit-identical to the
    single-device engine on the forced 4-device CPU mesh, across every
    engine property PRs 1-9 locked."""

    def test_greedy_streams_match_single_device_across_configs(self):
        """Engine vs engine, token for token — MHA (head-sharded),
        GQA+RoPE (kv_heads < tp: the replicated-KV fallback), windowed,
        and MoE (replicated experts)."""
        from kubeshare_tpu.serving import EngineConfig, Request, ServingEngine

        cases = {
            "mha": dict(),
            "gqa_rope": dict(n_kv_heads=2, positional="rope"),
            "windowed": dict(attention_window=6),
            "moe": dict(moe_every=2, moe_num_experts=4, moe_top_k=2),
        }
        base = dict(num_slots=3, block_size=4, num_blocks=41,
                    max_request_len=48, prefill_chunk=8)
        for name, extra in cases.items():
            config = _small_config(**extra)
            params = transformer_init(jax.random.PRNGKey(0), config)
            prompt = np.asarray(jax.random.randint(
                jax.random.PRNGKey(1), (13,), 0, 64), np.int32)
            single = ServingEngine(params, config, EngineConfig(**base))
            single.submit(Request("r0", prompt, 8))
            want = single.run()["r0"].tokens
            sharded = _sharded_engine(params, config)
            sharded.submit(Request("r0", prompt, 8))
            got = sharded.run()["r0"].tokens
            assert got == want, name
            expect_fallback = name == "gqa_rope"  # 2 KV heads < tp=4
            assert sharded._sharded.decision.attn_sharded != \
                expect_fallback, name

    def test_replicated_fallback_pool_and_params_stay_replicated(self):
        """kv_heads < tp: the pool and the attention weights replicate
        (sharding them is impossible without breaking GQA groups); the
        MLP halves still shard."""
        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _sharded_engine(params, config)
        assert engine.pool.k.sharding.is_fully_replicated
        assert engine.params["layers"][0]["attn"][
            "wq"].sharding.is_fully_replicated
        assert not engine.params["layers"][0]["mlp"][
            "w_in"].sharding.is_fully_replicated

    def test_head_sharded_pool_splits_kv_head_axis(self):
        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _sharded_engine(params, config)
        assert not engine.pool.k.sharding.is_fully_replicated
        # axis 2 of [n_layers, num_blocks, kv_heads, bs, head_dim]
        shard = next(iter(engine.pool.k.addressable_shards))
        assert shard.data.shape[2] == config.kv_heads // TP

    def test_indivisible_kv_heads_is_loud_at_engine_build(self):
        config = TransformerConfig(
            vocab_size=64, d_model=48, n_heads=12, n_layers=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32, attention="reference",
            n_kv_heads=6)
        params = transformer_init(jax.random.PRNGKey(0), config)
        with pytest.raises(ValueError, match="not divisible by tp=4"):
            _sharded_engine(params, config)

    def test_sampled_stream_matches_dense_oracle(self):
        """Same rng => the SHARDED engine reproduces the dense sampled
        oracle exactly (the single-device engine's locked contract,
        inherited bit-for-bit)."""
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
        engine = _sharded_engine(params, config, top_k=10, top_p=0.95)
        engine.submit(Request("r0", prompt, 6, temperature=0.8, rng=rng))
        assert engine.run()["r0"].tokens == list(dense)

    def test_zero_recompiles_after_warmup(self):
        """The acceptance bar's compile lock: warmup under the mesh
        compiles every dispatchable shape ONCE; a mixed-length workload
        (mid-flight admissions, ragged tails, CoW) adds zero."""
        from kubeshare_tpu.serving import Request

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _sharded_engine(params, config)
        engine.warmup()
        baseline = engine.compile_counts()
        rng = np.random.default_rng(3)
        shapes = [(1, 3), (5, 8), (13, 4), (21, 11), (29, 2)]
        for i, (length, new) in enumerate(shapes):
            engine.submit(Request(f"r{i}", rng.integers(0, 64, length),
                                  new))
        engine.run()
        assert engine.compile_counts() == baseline

    def test_device_loop_sharded_bit_exact(self):
        """The device-resident multi-step loop under tp: the while-loop
        and its collectives live inside ONE shard_map program (the cond
        reads only replicated values, so every device runs the same
        unit count) and the sharded K=4 engine emits EXACTLY the
        single-device K=1 streams — greedy and sampled — with the same
        ~K x planner-invocation drop and zero recompiles after warmup,
        ``compile_counts()[\"loop\"]`` included."""
        from kubeshare_tpu.serving import EngineConfig, Request, ServingEngine

        config = _small_config()  # 4 KV heads: head-sharded on tp=4
        params = transformer_init(jax.random.PRNGKey(0), config)
        base = dict(num_slots=3, block_size=4, num_blocks=41,
                    max_request_len=48, prefill_chunk=8,
                    top_k=10, top_p=0.95)
        rng = np.random.default_rng(9)
        reqs = [
            dict(rid="d", prompt=rng.integers(0, 64, 5),
                 max_new_tokens=24),
            dict(rid="s", prompt=rng.integers(0, 64, 13),
                 max_new_tokens=9, temperature=0.8,
                 rng=jax.random.PRNGKey(10)),
        ]
        single = ServingEngine(params, config, EngineConfig(**base))
        for req in reqs:
            single.submit(Request(**req))
        want = {rid: r.tokens for rid, r in single.run().items()}

        engine = _sharded_engine(params, config, steps_per_launch=4,
                                 top_k=10, top_p=0.95)
        engine.warmup()
        baseline = engine.compile_counts()
        assert baseline["loop"] >= 1
        for req in reqs:
            engine.submit(Request(**req))
        got = {rid: r.tokens for rid, r in engine.run().items()}
        assert got == want
        assert engine.loop_launches >= 1
        assert engine.host_planner_invocations < \
            single.host_planner_invocations
        assert engine.compile_counts() == baseline

    def test_cow_divergence_sharded(self):
        """Sharded CoW: a mid-block divergence copies the shared tail
        block through the shard_map copy twin, and neither the
        diverging stream nor the original's replay changes."""
        from kubeshare_tpu.models.decoding import greedy_decode

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(4)
        a = rng.integers(0, 64, 10)
        b = a.copy()
        b[9] = (b[9] + 7) % 64  # diverges at the tail block's 2nd row
        engine = _sharded_engine(params, config)
        got = _run_sequentially(engine, [
            dict(rid="a1", prompt=a, max_new_tokens=6),
            dict(rid="b", prompt=b, max_new_tokens=6),
            dict(rid="a2", prompt=a.copy(), max_new_tokens=6),
        ])
        assert engine.cow_copies >= 1
        assert engine.prefix_hit_requests >= 1  # a2 resumed off a1's blocks
        for rid, prompt in (("a1", a), ("b", b), ("a2", a)):
            ref = np.asarray(greedy_decode(
                params, config, jnp.asarray(prompt, jnp.int32)[None], 6))[0]
            assert got[rid] == list(ref), rid
        assert got["a1"] == got["a2"]

    def test_prefix_hit_sampled_sharded(self):
        """The key schedule survives a prefix-cache hit under the mesh:
        a sampled request admitted onto a matched prefix reproduces its
        solo dense stream."""
        from kubeshare_tpu.models.decoding import sample_decode

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(3), (14,), 0, 64), np.int32)
        rng = jax.random.PRNGKey(9)
        engine = _sharded_engine(params, config, top_k=10, top_p=0.95)
        got = _run_sequentially(engine, [
            dict(rid="warm", prompt=prompt, max_new_tokens=3),
            dict(rid="samp", prompt=prompt.copy(), max_new_tokens=5,
                 temperature=0.8, rng=rng),
        ])
        assert engine.prefix_hit_tokens == 13
        ref = np.asarray(sample_decode(
            params, config, jnp.asarray(prompt)[None], rng, 5,
            temperature=0.8, top_k=10, top_p=0.95))[0]
        assert got["samp"] == list(ref)

    def test_preemption_resume_sharded_bit_exact(self):
        """QoS preemption under the mesh: the Opportunistic victim's
        blocks retire into the (sharded) prefix cache and the resume
        emits exactly its unpreempted stream."""
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
        engine = ServingEngine(
            params, config,
            EngineConfig(num_slots=2, block_size=4, num_blocks=13,
                         max_request_len=32, prefill_chunk=8,
                         mesh_spec=TP_SPEC),
            tenants=registry)
        rng = np.random.default_rng(21)
        p_batch = rng.integers(0, 64, 17)  # 17 + 14 = 31 rows -> 8 blocks
        p_gold = rng.integers(0, 64, 18)   # 18 + 6 = 24 rows -> 6 blocks
        engine.submit(Request("victim", p_batch, 14, tenant="batch"))
        while True:  # drive the victim mid-decode before gold arrives
            slots = [s for s in engine._slots if s.rid == "victim"
                     and s.state == "decode"]
            if slots and len(slots[0].generated) >= 2:
                break
            assert engine.step(), "engine idle before victim decoded"
        engine.submit(Request("gold", p_gold, 6, tenant="gold"))
        out = engine.run()
        assert engine.preemptions.get("batch", 0) >= 1
        for rid, prompt, new in (("victim", p_batch, 14),
                                 ("gold", p_gold, 6)):
            ref = np.asarray(greedy_decode(
                params, config, jnp.asarray(prompt, jnp.int32)[None],
                new))[0]
            assert out[rid].tokens == list(ref), rid
        assert engine.prefix_hit_requests >= 1

    def test_speculative_sharded_bit_exact(self):
        """Speculation under the mesh: verify chunks run through the
        shard_map twin and the streams stay the non-speculative (and
        dense-oracle) streams exactly."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(52)
        base = rng.integers(0, 64, 6)
        # repetitive prompts (the traffic speculation exists for) plus
        # an incompressible control lane riding verify at width 1
        reqs = [
            dict(rid="rep0", prompt=np.tile(base, 4)[:22],
                 max_new_tokens=10),
            dict(rid="rep1", prompt=np.tile(rng.integers(0, 64, 4),
                                            5)[:17], max_new_tokens=8),
            dict(rid="rand", prompt=rng.integers(0, 64, 9),
                 max_new_tokens=6),
        ]
        engine = _sharded_engine(params, config, speculative=True,
                                 draft_len=4)
        for req in reqs:
            engine.submit(Request(**req))
        got = {rid: r.tokens for rid, r in engine.run().items()}
        for req in reqs:
            ref = np.asarray(greedy_decode(
                params, config,
                jnp.asarray(req["prompt"], jnp.int32)[None],
                req["max_new_tokens"]))[0]
            assert got[req["rid"]] == list(ref), req["rid"]
        assert engine.verify_steps > 0
        assert sum(engine.spec_drafted.values()) > 0

    def test_spec_loop_sharded_bit_exact(self):
        """Device residency v2 under the mesh: verify-in-loop launches
        (with the admission ring armed) run through the shard_map twin
        — the loop cond gathers logits so every device computes
        identical picks, alive masks and ring heads — and the streams
        are BIT-IDENTICAL to the single-device non-loop speculative
        engine's, greedy AND sampled, zero recompiles after warmup."""
        from kubeshare_tpu.serving import (EngineConfig, Request,
                                           ServingEngine)

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(57)
        reqs = []
        for i in range(5):
            pat = rng.integers(0, 64, 4)
            prompt = np.concatenate([np.tile(pat, 3),
                                     rng.integers(0, 64, 2)])
            req = dict(rid=f"r{i}", prompt=prompt, max_new_tokens=9)
            if i in (1, 3):
                req.update(temperature=0.8,
                           rng=jax.random.PRNGKey(58 + i))
            reqs.append(req)
        kwargs = dict(speculative=True, draft_len=4, top_k=10,
                      top_p=0.95)
        engine = _sharded_engine(params, config, steps_per_launch=4,
                                 admission_ring=2, **kwargs)
        engine.warmup()
        baseline = engine.compile_counts()
        assert baseline["spec_loop"] >= 1
        for req in reqs:
            engine.submit(Request(**req))
        got = {rid: r.tokens for rid, r in engine.run().items()}
        oracle = ServingEngine(params, config, EngineConfig(
            num_slots=3, block_size=4, num_blocks=41,
            max_request_len=48, prefill_chunk=8, **kwargs))
        for req in reqs:
            oracle.submit(Request(**req))
        want = {rid: r.tokens for rid, r in oracle.run().items()}
        assert got == want
        assert engine.spec_loop_launches > 0
        assert engine.spec_loop_units > 0
        assert engine.compile_counts() == baseline

    def test_long_context_threshold_routes_bit_exact(self):
        """Past the threshold, prefill chunks re-shard Ulysses-style
        (sequence-parallel attention inside the program) — and the
        stream does not move by a bit."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request

        config = _small_config()  # 4 KV heads: head-sharded
        params = transformer_init(jax.random.PRNGKey(0), config)
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (21,), 0, 64), np.int32)
        ref = np.asarray(greedy_decode(
            params, config, jnp.asarray(prompt)[None], 6))[0]
        # two full 8-wide chunks route through Ulysses; the ragged
        # 5-wide tail stays head-parallel (below the threshold)
        engine = _sharded_engine(params, config,
                                 long_context_threshold=8)
        engine.submit(Request("r0", prompt, 6))
        assert engine.run()["r0"].tokens == list(ref)
        assert engine._sharded.decision.attn_sharded

    def test_long_context_threshold_requires_mesh(self):
        from kubeshare_tpu.serving import EngineConfig, ServingEngine

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        with pytest.raises(ValueError, match="requires mesh_spec"):
            ServingEngine(params, config, EngineConfig(
                num_slots=3, block_size=4, num_blocks=41,
                max_request_len=48, prefill_chunk=8,
                long_context_threshold=8))

    def test_tier_roundtrip_sharded(self):
        """KV tiering under the mesh: demotion gathers sharded blocks
        to host wire bytes, promotion re-scatters them through the
        sharded upload twin — streams stay the dense oracle's."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _sharded_engine(params, config, num_slots=1,
                                 num_blocks=13, max_request_len=32,
                                 host_tier_bytes=1 << 20)
        rng = np.random.default_rng(11)
        shared = rng.integers(0, 64, 13)
        reqs = [
            dict(rid="r0", prompt=shared, max_new_tokens=3),
            dict(rid="f1", prompt=rng.integers(0, 64, 29),
                 max_new_tokens=3),
            dict(rid="f2", prompt=rng.integers(0, 64, 29),
                 max_new_tokens=3),
            dict(rid="hit", prompt=np.concatenate(
                [shared, rng.integers(0, 64, 4)]), max_new_tokens=3),
        ]
        got = _run_sequentially(engine, reqs)
        assert engine.tier_demoted_blocks > 0
        assert engine.tier_promoted_blocks > 0
        assert engine.tier_hit_requests > 0
        for req in reqs:
            ref = np.asarray(greedy_decode(
                params, config,
                jnp.asarray(req["prompt"], jnp.int32)[None],
                req["max_new_tokens"]))[0]
            assert got[req["rid"]] == list(ref), req["rid"]

    def test_collective_bytes_counter_and_tp_label(self):
        """Satellite: the sharded engine's dispatch families carry the
        tp constant-label and the collective-bytes counter accumulates
        from shard shapes; a single-device engine exports neither."""
        from kubeshare_tpu.serving import EngineConfig, Request, ServingEngine
        from kubeshare_tpu.utils.promtext import encode_families

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        engine = _sharded_engine(params, config)
        engine.submit(Request(
            "r0", np.asarray(jax.random.randint(
                jax.random.PRNGKey(1), (13,), 0, 64), np.int32), 8))
        engine.run()
        assert engine.collective_bytes["prefill_chunk"] > 0
        assert engine.collective_bytes["decode_span"] > 0
        text = encode_families(engine.collect_metrics())
        assert 'tp="4"' in text
        assert "kubeshare_serving_collective_bytes_total" in text
        plain = ServingEngine(
            params, config,
            EngineConfig(num_slots=3, block_size=4, num_blocks=41,
                         max_request_len=48, prefill_chunk=8))
        assert all(v == 0 for v in plain.collective_bytes.values())
        ptext = encode_families(plain.collect_metrics())
        assert 'tp="' not in ptext
