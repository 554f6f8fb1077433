"""Long-context training walkthrough: the round-3 parallelism stack.

Trains the flagship Transformer on synthetic next-token data over a
dp x tp x sp mesh with every long-context piece engaged:

  - zero-style (FSDP) parameter + optimizer sharding over dp
  - load-balanced ZIGZAG ring attention over sp (tokens permuted once,
    every ring step equal work, hand-scheduled backward)
  - optionally the 1F1B pipeline schedule with ring attention in-stage
    (pp x sp composition, full-parameter gradients)

Run on the CPU mesh (no TPU needed):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m examples.train_longcontext
    ... --pp        # 1F1B x sp instead of dp x sp

On a real slice the same code runs with the actual device mesh; only the
mesh spec and sizes change.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=4)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--pp", action="store_true",
                        help="1F1B pipeline x sp instead of dp x sp")
    args = parser.parse_args()

    from kubeshare_tpu.models.transformer import (
        TransformerConfig,
        transformer_apply_ring,
        transformer_fsdp_rules,
        transformer_init,
        transformer_train_1f1b,
    )
    from kubeshare_tpu.parallel import MeshSpec, batch_sharding, make_mesh
    from kubeshare_tpu.parallel.mesh import shard_params
    from kubeshare_tpu.parallel.train import cross_entropy_loss

    config = TransformerConfig(
        vocab_size=256, d_model=64, n_heads=8, n_layers=4, d_ff=128,
        max_seq_len=args.seq, dtype=jnp.float32, attention="ring",
        positional="rope",
    )
    params = transformer_init(jax.random.PRNGKey(0), config)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, config.vocab_size, (args.batch, args.seq)),
        jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)

    optimizer = optax.adamw(3e-4)

    if args.pp:
        # 1F1B x sp: microbatches hop pipeline stages while ring attention
        # runs over sp inside each stage; gradients cover every parameter
        from jax.sharding import Mesh

        devices = jax.devices()
        if len(devices) < 2:
            raise SystemExit(
                "--pp needs >= 2 devices; set JAX_PLATFORMS=cpu "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        pp, sp = 2, max(len(devices) // 2, 1)
        mesh = Mesh(np.array(devices[:pp * sp]).reshape(pp, sp),
                    ("pp", "sp"))
        print(f"mesh: 1f1b pp={pp} x sp={sp} (ring attention in-stage)")
        opt_state = optimizer.init(params)

        @jax.jit
        def step(params, opt_state, tokens, targets):
            loss, grads = transformer_train_1f1b(
                params, tokens, targets, config, mesh, num_microbatches=2)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        losses = []
        for i in range(args.steps):
            params, opt_state, loss = step(params, opt_state, tokens,
                                           targets)
            losses.append(float(loss))
            print(f"step {i}: loss {losses[-1]:.4f}")
        assert losses[-1] < losses[0], "loss did not improve"
        print("long-context training demo complete")
        return 0

    # dp x sp: FSDP-sharded params + zigzag ring attention
    spec = MeshSpec(dp=2, tp=2, sp=2)
    mesh = make_mesh(spec)
    print(f"mesh: dp={spec.dp} x tp={spec.tp} x sp={spec.sp}, "
          "fsdp params, zigzag ring")
    params = shard_params(params, transformer_fsdp_rules(), mesh)
    opt_state = optimizer.init(params)  # moments inherit the sharding
    data_sharding = batch_sharding(mesh, ndim=2)
    tokens = jax.device_put(tokens, data_sharding)
    targets = jax.device_put(targets, data_sharding)

    @jax.jit
    def step(params, opt_state, tokens, targets):
        def loss_fn(p):
            logits = transformer_apply_ring(
                p, tokens, config, mesh, layout="zigzag", use_flash=False)
            return cross_entropy_loss(logits, targets)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
        print(f"step {i}: loss {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "loss did not improve"
    print("long-context training demo complete")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
