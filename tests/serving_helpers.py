"""What the serving test files (``tests/test_serving*.py``,
``tests/test_packed_args.py``) share: the tiny model, the model whose
continuation a test controls, the engine at the suite's geometry, and the
four pool-writing step programs as cases."""

import jax
import jax.numpy as jnp

from kubeshare_tpu.models.transformer import TransformerConfig, transformer_init


def _small_config(**extra):
    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32, attention="reference", **extra)


def _cyclic_params(config):
    """Weights of a model whose continuation the test controls, for the
    tests that need drafts ACCEPTED: `transformer_init`'s, with every
    layer's `wo` and `w_out` (and `pos_embed`) zeroed, so the residual
    stream is the last token's embedding, and an `lm_head` that reads
    the embedding of `t` back as `t ^ 1`.  Greedy decoding alternates between two ids from its first
    token on, whatever the prompt, so the n-gram drafter proposes what
    the model emits from the fourth token; the head is scaled up so that
    sampled lanes mostly follow the cycle too.  The random-weight model
    of the other tests repeats only by luck."""
    params = transformer_init(jax.random.PRNGKey(0), config)
    embed = params["embed"]
    successor = jnp.arange(embed.shape[0]) ^ 1
    layers = []
    for layer in params["layers"]:
        layer = dict(layer)
        layer["attn"] = dict(layer["attn"],
                             wo=jnp.zeros_like(layer["attn"]["wo"]))
        layer["mlp"] = dict(layer["mlp"],
                            w_out=jnp.zeros_like(layer["mlp"]["w_out"]))
        layers.append(layer)
    out = dict(params, layers=layers, lm_head=8.0 * embed[successor].T)
    if "pos_embed" in params:
        out["pos_embed"] = jnp.zeros_like(params["pos_embed"])
    normed = embed * jax.lax.rsqrt(jnp.mean(embed ** 2, -1, keepdims=True))
    logits = normed @ out["lm_head"]
    assert (jnp.argmax(logits, -1) == successor).all()
    return out


def _engine(params, config, **overrides):
    from kubeshare_tpu.serving import EngineConfig, ServingEngine

    kwargs = dict(num_slots=3, block_size=4, num_blocks=41,
                  max_request_len=48, prefill_chunk=8)
    kwargs.update(overrides)
    return ServingEngine(params, config, EngineConfig(**kwargs))


def _all_eqns(jaxpr):
    """Every equation of ``jaxpr``, sub-jaxprs (jit, scan, shard_map)
    included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _all_eqns(sub)


def _pool_step_case(name):
    """(fn(params, pool_k, pool_v), params, pool shape, pool shape on one
    device) for one of the four layer loops that write the pool, at a tiny
    GQA + rope config with every lane live."""
    from kubeshare_tpu.serving.paged import (
        paged_decode_step, paged_prefill_step, paged_verify_span)

    config = _small_config(n_kv_heads=2, positional="rope")
    params = transformer_init(jax.random.PRNGKey(0), config)
    lanes, width, chunk, bs, blocks = 3, 5, 4, 4, 17
    shape = (config.n_layers, blocks, config.kv_heads, bs, config.head_dim)
    tables = jnp.arange(1, 1 + lanes * width, dtype=jnp.int32).reshape(
        lanes, width)
    lengths = jnp.asarray([3, 6, 9], jnp.int32)
    active = jnp.ones((lanes,), bool)
    chunk_tokens = jnp.ones((lanes, chunk), jnp.int32)
    last_rows = jnp.zeros((lanes,), jnp.int32)

    if name == "paged_prefill_step":
        return (lambda w, pk, pv: paged_prefill_step(
            w, config, pk, pv, tables, lengths, active, chunk_tokens,
            last_rows), params, shape, shape)
    if name == "paged_decode_step":
        return (lambda w, pk, pv: paged_decode_step(
            w, config, pk, pv, tables, lengths, active,
            jnp.ones((lanes,), jnp.int32)), params, shape, shape)
    if name == "paged_verify_span":
        def pick(logits, temps, keys):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        return (lambda w, pk, pv: paged_verify_span(
            w, config, pick, pk, pv, tables, lengths, active, chunk_tokens,
            jnp.full((lanes,), chunk, jnp.int32),
            jnp.zeros((lanes,), jnp.float32),
            jnp.zeros((lanes, chunk, 2), jnp.uint32)), params, shape, shape)
    # sharded._chunk_stack, through the shard_map twin of the prefill
    # step: each of two devices holds one of the two KV heads
    from kubeshare_tpu.parallel.mesh import MeshSpec
    from kubeshare_tpu.serving.sharded import ShardedServingContext

    assert name == "sharded_prefill"
    ctx = ShardedServingContext(config, MeshSpec(dp=1, tp=2, sp=1), params)
    assert ctx.decision.attn_sharded
    return (lambda w, pk, pv: ctx.prefill(
        w, pk, pv, tables, lengths, active, chunk_tokens, last_rows),
        ctx.place_params(params), shape, shape[:2] + (1,) + shape[3:])
