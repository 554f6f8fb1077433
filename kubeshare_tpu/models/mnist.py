"""MNIST CNN — the north-star workload (BASELINE.md: two 0.5-chip MNIST
pods co-run on one chip).  The reference schedules PyTorch MNIST pods
(ref test/mnist/mnist1.yaml); this is the TPU-native equivalent the e2e
tests run under token gating.

Functional-pytree style: init returns params, apply is pure — jit/pjit
compose without a framework dependency.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class MnistConfig:
    num_classes: int = 10
    channels1: int = 32
    channels2: int = 64
    hidden: int = 128
    image_size: int = 28


def mnist_init(rng: jax.Array, config: MnistConfig = MnistConfig()) -> Dict:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    reduced = config.image_size // 4  # two stride-2 pools
    flat = reduced * reduced * config.channels2

    def conv_init(key, shape):
        fan_in = shape[0] * shape[1] * shape[2]
        return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5

    def dense_init(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * (2.0 / shape[0]) ** 0.5

    return {
        "conv1": {"w": conv_init(k1, (3, 3, 1, config.channels1)),
                  "b": jnp.zeros((config.channels1,))},
        "conv2": {"w": conv_init(k2, (3, 3, config.channels1, config.channels2)),
                  "b": jnp.zeros((config.channels2,))},
        "dense1": {"w": dense_init(k3, (flat, config.hidden)),
                   "b": jnp.zeros((config.hidden,))},
        "dense2": {"w": dense_init(k4, (config.hidden, config.num_classes)),
                   "b": jnp.zeros((config.num_classes,))},
    }


def mnist_apply(params: Dict, images: jax.Array) -> jax.Array:
    """images: [batch, 28, 28, 1] -> logits [batch, classes]."""
    x = images
    for layer in ("conv1", "conv2"):
        x = jax.lax.conv_general_dilated(
            x, params[layer]["w"], window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + params[layer]["b"]
        x = jax.nn.relu(x)
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
        )
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["dense1"]["w"] + params["dense1"]["b"])
    return x @ params["dense2"]["w"] + params["dense2"]["b"]
