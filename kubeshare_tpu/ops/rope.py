"""Rotary position embeddings (RoPE).

Relative-position encoding applied to Q/K after projection — the modern
default for decoder LMs, and the right fit for the sequence-sharded paths:
each shard rotates by its *global* positions (pass ``offset``), so ring
attention and KV-cache decoding stay exact without learned-position tables.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    """Inverse frequencies [head_dim/2] (f32)."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float = 10000.0,
    interleaved: bool = False,
) -> jax.Array:
    """Rotate [batch, heads, seq, head_dim] by per-token positions.

    ``positions`` [seq] shares positions across the batch; [batch, seq]
    rotates every batch row by its OWN positions — the paged serving
    pool, where each slot sits at its own decode length
    (serving/paged.py).  Split-half convention: pairs
    (x[..., :d/2], x[..., d/2:]).  ``interleaved``: the pairs are
    (x[..., 2i], x[..., 2i+1]) instead; the result comes back
    de-interleaved (evens' half, then odds'), which leaves every dot
    product between two vectors rotated this way what it is.
    """
    d = x.shape[-1]
    inv_freq = rope_frequencies(d, theta)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [(b,)s, d/2]
    if positions.ndim == 1:
        cos = jnp.cos(angles)[None, None, :, :]
        sin = jnp.sin(angles)[None, None, :, :]
    else:
        cos = jnp.cos(angles)[:, None, :, :]  # [b, 1, s, d/2]
        sin = jnp.sin(angles)[:, None, :, :]
    if interleaved:
        x1 = x[..., 0::2].astype(jnp.float32)
        x2 = x[..., 1::2].astype(jnp.float32)
    else:
        x1 = x[..., : d // 2].astype(jnp.float32)
        x2 = x[..., d // 2 :].astype(jnp.float32)
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return rotated.astype(x.dtype)


def rope_positions(seq_len: int, offset: jax.Array | int = 0) -> jax.Array:
    """Global positions for a (possibly sequence-sharded) block."""
    return jnp.arange(seq_len, dtype=jnp.int32) + offset
