"""The 'latent_shortcut' block at tiny widths: two latent (MLA) attentions,
two gated FFNs and one shortcut-connected expert layer with zero-compute
experts a double layer, served through a paged latent cache by one
expert-parallel rank — against the plain reference of the benchmark
(``chipbench/longcat_flash_reference.py``: float32, the expanded attention,
every held expert on every row, nothing imported from the program).  What
the two latent kinds must do alike is in ``tests/test_latent_moe_block.py``.

Sizes: d 64, 4 heads, ranks 32 / 16, nope 16 / rope 8 / v 16, 2 double
layers, 16 routed + 8 zero experts, top 4, 4 held by rank 0.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from latent_kinds import (BLOCK, KINDS, REPO, ROWS, config_of, jitted_steps,
                          params_of, served_logits)
from kubeshare_tpu.models.decoding import greedy_decode
from kubeshare_tpu.models.transformer import (
    TransformerConfig, latent_attend, latent_qkv, transformer_apply)
from kubeshare_tpu.ops.moe import routed_experts_apply
from kubeshare_tpu.serving import EngineConfig, Request, ServingEngine
from kubeshare_tpu.serving.kv_blocks import init_paged_pool, kv_row_layout
from kubeshare_tpu.serving import paged

KIND = "latent_shortcut"
TC = KINDS[KIND].tc
reference, weights = KINDS[KIND].reference, KINDS[KIND].weights
_served_logits = served_logits
_jitted_steps = jitted_steps


def _config(dtype, **changes):
    return config_of(KIND, dtype, **changes)


def _params(seed, dtype):
    return params_of(KIND, seed, dtype)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, TC["vocab_size"], 40) \
        .astype(np.int32)


class TestAgainstThePlainReference:
    PROMPT = 27  # three whole chunks of 8 and a ragged one

    def test_float32_program_is_the_reference(self, tokens):
        """(a) In float32 the paged path — chunked prefill into the latent
        pool, then decode in the absorbed form — and the reference's full
        forward differ by summation order alone: 2e-4 on logits of
        standard deviation 1.  Leaving out a scale, the identity experts'
        term, the shortcut or the rope moves logits by 0.05 and more."""
        params, config = _params(11, jnp.float32), _config("float32")
        served = _served_logits(params, config, tokens, self.PROMPT)
        rows = np.arange(self.PROMPT - 1, len(tokens))
        ref = reference.reference_logits(params, TC, tokens, rows)
        assert np.abs(served - ref).max() < 2e-4
        unpaged = np.asarray(transformer_apply(
            params, jnp.asarray(tokens)[None], config)[0])[rows]
        assert np.abs(unpaged - ref).max() < 2e-4

    def test_the_view_in_small_key_blocks_is_the_same(self, tokens,
                                                      monkeypatch):
        """The paged steps attend a lane's view a key block at a time, as
        far as the lanes reach (one block at these sizes): in blocks of 8
        rows, five of them by the last row, the logits are the
        reference's still."""
        monkeypatch.setattr(paged, "KEY_BLOCK", 8)
        params, config = _params(11, jnp.float32), _config("float32")
        served = _served_logits(params, config, tokens, self.PROMPT,
                                steps=_jitted_steps())
        rows = np.arange(self.PROMPT - 1, len(tokens))
        ref = reference.reference_logits(params, TC, tokens, rows)
        assert np.abs(served - ref).max() < 2e-4

    def test_bfloat16_program_is_nearer_than_fp8(self, tokens):
        """(a) As served (bf16) the logits lie a mean 0.014 from the
        reference's (rounding, and a router choice decided the other way
        now and then: up to 0.3 at one row, so the mean is what is held);
        the reference's own pass in fp8 lies 0.06 and more from it.  The
        limit is between: a program computing in fp8 fails it."""
        params, config = _params(11, jnp.bfloat16), _config("bfloat16")
        served = _served_logits(params, config, tokens, self.PROMPT)
        rows = np.arange(self.PROMPT - 1, len(tokens))
        ref = reference.reference_logits(params, TC, tokens, rows)
        low = reference.reference_logits(params, TC, tokens, rows, low="fp8")
        limit = 0.03
        assert np.abs(served - ref).mean() < limit
        assert np.abs(low - ref).mean() > limit


def test_absorbed_and_expanded_attention_agree():
    """(b) The two forms of the latent attention are the same numbers."""
    config = _config("float32")
    attn = _params(3, jnp.float32)["layers"][0]["attn"][0]
    rng = np.random.default_rng(1)
    b, c, v = 2, 5, 24
    y = jnp.asarray(rng.normal(size=(b, c, TC["d_model"])), jnp.float32)
    positions = jnp.asarray([[7, 8, 9, 10, 11], [19, 20, 21, 22, 23]])
    q_nope, q_rope, _, _ = latent_qkv(attn, y, positions, config)
    view_c = jnp.asarray(rng.normal(size=(b, v, TC["kv_lora_rank"])),
                         jnp.float32)
    view_r = jnp.asarray(rng.normal(size=(b, v, TC["qk_rope_head_dim"])),
                         jnp.float32)
    out = [latent_attend(attn, q_nope, q_rope, view_c, view_r, positions,
                         config, absorbed) for absorbed in (True, False)]
    assert out[0].shape == (b, c, TC["d_model"])
    np.testing.assert_allclose(out[0], out[1], atol=2e-5, rtol=1e-5)


def _expert_case(seed=4, n=24):
    moe = _params(seed, jnp.float32)["layers"][0]["moe"]
    uncut = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        weights.make_weights(seed, {**TC, "experts_held": None}))
    y = jnp.asarray(np.random.default_rng(seed).normal(
        size=(n, TC["d_model"])), jnp.float32)
    return moe, uncut["layers"][0]["moe"], y


def _apply(moe, y, first_held=0):
    return routed_experts_apply(
        moe, y, n_routed=TC["n_routed_experts"], top_k=TC["router_top_k"],
        scale=TC["routed_scaling_factor"], first_held=first_held)


def _sizes(**changes):
    return reference._sizes({**TC, **changes})


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """(c) Over the 4 ranks that share the layer, each holding 4 of the 16
    routed experts: the held parts summed, and the identity experts' part
    (which every rank computes alike) counted once, are the reference's
    uncut layer."""
    _, uncut, y = _expert_case()
    whole = reference.expert_layer(y, uncut, _sizes())
    empty = {k: (v if k == "router" else v[:0]) for k, v in uncut.items()}
    identity = reference.expert_layer(y, empty, _sizes())
    total, counts = identity, np.zeros((6,), np.int64)
    for rank in range(4):
        share = {k: (v if k == "router" else v[4 * rank:4 * rank + 4])
                 for k, v in uncut.items()}
        out, c = _apply(share, y, first_held=4 * rank)
        np.testing.assert_allclose(
            out, reference.expert_layer(y, share,
                                        _sizes(first_expert_held=4 * rank)),
            atol=1e-5)
        total = total + (out - identity)
        counts += np.asarray(c)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    # every choice is held by exactly one rank or is an identity expert
    n_choices = y.shape[0] * TC["router_top_k"]
    assert counts[0] + counts[1] // 4 == n_choices
    assert all(c[0] + c[1] + c[2] == n_choices
               for c in [np.asarray(_apply(uncut, y)[1])])


def _forced_router(moe, chosen):
    """The router with a row's scores pinned on ``chosen``."""
    router = np.asarray(moe["router"]).copy() * 1e-3
    router[:, chosen] += np.linspace(2.0, 1.0, len(chosen))[None, :] \
        * np.sign(np.ones((router.shape[0], 1)))
    return {**moe, "router": jnp.asarray(router)}


def test_nothing_is_dropped_at_any_skew():
    """(d) Every row sent to the same held experts — 300 rows, 4 choices
    each, three tiles of 128 rows an expert, the last one padded: the
    result is still the reference's, which runs every expert on every
    row."""
    moe, _, y = _expert_case(n=300)
    y = jnp.abs(y)  # so that the pinned scores win on every row
    for chosen in ([0, 1, 2, 3], [0, 5, 9, 13]):
        forced = _forced_router(moe, chosen)
        out, counts = _apply(forced, y)
        np.testing.assert_allclose(
            out, reference.expert_layer(y, forced, _sizes()), atol=2e-5)
        held = sum(1 for e in chosen if e < 4)
        assert list(np.asarray(counts)) == [
            300 * held, 0, 300 * (4 - held), held, 3 * held, 384 * held]


def test_identity_experts_alone_weigh_the_input():
    """(e) A row that chooses only zero-compute experts gets
    ``6 . sum P_e . y``, and no held expert is touched."""
    moe, _, y = _expert_case()
    y = jnp.abs(y)
    forced = _forced_router(moe, [16, 18, 20, 23])
    out, counts = _apply(forced, y)
    probs = jax.nn.softmax(y @ forced["router"], -1)
    weight = 6.0 * jax.lax.top_k(probs, 4)[0].sum(-1, keepdims=True)
    np.testing.assert_allclose(out, weight * y, rtol=1e-5, atol=1e-6)
    assert list(np.asarray(counts)) == [0, 24 * 4, 0, 0, 0, 0]


@pytest.mark.parametrize("name,pool_shape", [
    ("starcoderbase-1b", (24, 8193, 1, 16, 128)),
    ("starcoder2-3b", (30, 2185, 2, 16, 128))])
def test_the_dense_configurations_are_what_they_were(name, pool_shape):
    """(f) Every new field defaults to what the dense block does: the two
    configuration files build the object they built, and their pools."""
    with open(os.path.join(REPO, "chipbench", "configs",
                           f"{name}.json")) as f:
        config_file = json.load(f)
    tc = dict(config_file["transformer_config"])
    tc["dtype"] = jnp.dtype(tc["dtype"])
    config = TransformerConfig(**tc)
    fresh = TransformerConfig()
    for field in dataclasses.fields(TransformerConfig):
        expected = tc.get(field.name, getattr(fresh, field.name))
        assert getattr(config, field.name) == expected, field.name
    assert not config.latent and config.attn_sublayers == config.n_layers
    layout = kv_row_layout(config)
    assert layout.kind == "kv_heads" and layout.k_row == layout.v_row
    e = config_file["engine"]
    per_block = layout.values_per_row() * 2 * e["block_size"]
    num_blocks = e["pool_bytes"] // per_block + 1
    k, v = jax.eval_shape(lambda: dataclasses.astuple(init_paged_pool(
        config, num_blocks, e["block_size"]))[:2])
    assert k.shape == v.shape == pool_shape


def test_the_float32_engine_serves_the_dense_caches_streams():
    config = _config("float32")
    params = _params(5, jnp.float32)
    engine = ServingEngine(params, config, EngineConfig(
        num_slots=3, block_size=BLOCK, num_blocks=64, max_request_len=ROWS,
        prefill_chunk=8))
    rng = np.random.default_rng(3)
    reqs = [(f"r{i}", rng.integers(0, TC["vocab_size"], n), new)
            for i, (n, new) in enumerate([(5, 6), (13, 4), (21, 5)])]
    for rid, prompt, new in reqs:
        engine.submit(Request(rid, prompt, new))
    out = engine.run()
    for rid, prompt, new in reqs:
        # the dense latent cache of models/decoding.py: the same math over
        # a lockstep cache, itself held to the unpaged forward below
        ref = np.asarray(greedy_decode(
            params, config, jnp.asarray(prompt, jnp.int32)[None], new))[0]
        assert out[rid].tokens == list(ref), rid
    _, prompt, new = reqs[1]
    toks = list(prompt)
    # one shape: rows after the last real one are causally dead
    forward = jax.jit(lambda t: transformer_apply(params, t, config))
    for _ in range(new):
        padded = np.zeros((1, 32), np.int32)
        padded[0, :len(toks)] = toks
        toks.append(int(jnp.argmax(
            forward(jnp.asarray(padded))[0, len(toks) - 1])))
    assert out["r1"].tokens == toks[len(prompt):]


def _refusals():
    from kubeshare_tpu.parallel.mesh import MeshSpec
    from kubeshare_tpu.serving.disagg import DisaggRouter
    from kubeshare_tpu.serving.fleet import ReplicaFleet
    from kubeshare_tpu.serving.kv_tier import HostTier, LRUTierPolicy
    from kubeshare_tpu.serving.sharded import ShardedServingContext

    small = dict(num_slots=2, block_size=BLOCK, num_blocks=16,
                 max_request_len=32, prefill_chunk=8)
    engine = lambda **kw: (lambda p, c: ServingEngine(
        p, c, EngineConfig(**{**small, **{k: v for k, v in kw.items()
                                          if k != "shared"}}),
        shared_host_tier=kw.get("shared")))
    return {
        "speculative": engine(speculative=True),
        "device_loop": engine(steps_per_launch=2),
        "mesh_spec": engine(mesh_spec=MeshSpec(tp=1)),
        "kv_tier": engine(host_tier_bytes=1 << 20),
        "shared_tier": engine(
            shared=HostTier(1 << 20, LRUTierPolicy())),
        "disagg_pool": engine(pool_role="prefill", mixed=False),
        "sharded_context": lambda p, c: ShardedServingContext(
            c, MeshSpec(tp=1), p),
        "disagg_router": lambda p, c: DisaggRouter(
            p, c, EngineConfig(**small, pool_role="prefill", mixed=False),
            EngineConfig(**small, pool_role="decode", mixed=False)),
        "fabric_fleet": lambda p, c: ReplicaFleet(
            p, c, EngineConfig(**small), replicas=1,
            shared_tier_bytes=1 << 20),
    }


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("what", [
    "speculative", "device_loop", "mesh_spec", "kv_tier", "shared_tier",
    "disagg_pool", "sharded_context", "disagg_router", "fabric_fleet"])
def test_what_does_not_serve_the_latent_row_says_so(what, kind):
    """Each refuses at construction, naming the layout, whichever latent
    kind asks."""
    config = config_of(kind, "bfloat16")
    params = params_of(kind, 5, jnp.bfloat16)
    with pytest.raises(ValueError, match=r"'latent'.*row"):
        _refusals()[what](params, config)
