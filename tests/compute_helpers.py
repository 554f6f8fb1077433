"""What the compute test files (``tests/test_compute*.py``) share."""

import jax
import jax.numpy as jnp


def rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
