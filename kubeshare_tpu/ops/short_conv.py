"""The gated short convolution: a layer's operator where a model's layers
name one (``TransformerConfig.layer_operators``: "conv").

    [B | C | u] = y W_in                  (thirds of 3 d columns, in that order)
    g_t = B_t * u_t
    c_t = sum_j w[:, j] * g_{t - (L - 1) + j}    (j = 0 .. L - 1, depthwise,
                                                  zeros before row 0, no bias)
    out_t = (C_t * c_t) W_out

``L`` = ``conv_taps`` (3: a row sees its own ``g`` and the two before it).
No positions, no softmax and nothing that grows with the request: what a
row needs of the rows before it is the last ``L - 1`` rows of ``g``, the
STATE — two rows of ``d`` values a lane at L = 3.  The filter runs as
``L`` shifted multiply-adds over ``[state | g]`` accumulated in float32,
the same code for a whole sequence (state zeros), a prefill chunk (the
state the chunk before left) and a decode step (one row), so the three
agree to the order of one sum of ``L`` products.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def conv_gates(conv: Dict, y: jax.Array, dtype
               ) -> Tuple[jax.Array, jax.Array]:
    """The operator's projections of ``y`` [..., d], a row's own whatever
    rows stand beside it: (``C`` the output's gate, ``g = B * u`` what the
    filter runs over), each [..., d]."""
    with jax.named_scope("short_conv"):
        d = y.shape[-1]
        bcu = y @ conv["w_in"].astype(dtype)
        gate_b, gate_c, u = (bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:])
        return gate_c, gate_b * u


def conv_filter(conv: Dict, g: jax.Array, state: jax.Array, dtype
                ) -> Tuple[jax.Array, jax.Array]:
    """The filter over ``g`` [B, C, d], lane by lane, behind ``state``
    [B, L - 1, d] -> (``c`` [B, C, d] in ``dtype``, ``window`` [B, L - 1 + C,
    d] = ``[state | g]``): the one part of the operator in which a row
    sees other rows."""
    with jax.named_scope("short_conv"):
        window = jnp.concatenate([state.astype(dtype), g], axis=1)
        taps = conv["filter"].astype(jnp.float32)
        rows = g.shape[1]
        c = sum(taps[j] * window[:, j:j + rows].astype(jnp.float32)
                for j in range(taps.shape[0]))
        return c.astype(dtype), window


def conv_out(conv: Dict, gate_c: jax.Array, c: jax.Array, dtype) -> jax.Array:
    """The gated output projection ``(C * c) W_out``, a row's own."""
    with jax.named_scope("short_conv"):
        return (gate_c * c) @ conv["w_out"].astype(dtype)


def short_conv(conv: Dict, y: jax.Array, state: jax.Array, dtype
               ) -> Tuple[jax.Array, jax.Array]:
    """The operator over ``y`` [B, C, d] whose lanes' earlier rows left
    ``state`` [B, L - 1, d] (the ``g`` of rows ``t - L + 1 .. t - 1``,
    oldest first; zeros where the chunk starts at row 0).  ``conv`` holds
    ``w_in`` [d, 3 d], ``filter`` [L, d] (tap j weighs the row ``L - 1 -
    j`` back) and ``w_out`` [d, d].  Returns (out [B, C, d], ``window``
    [B, L - 1 + C, d] = ``[state | g]``: the state after n of the chunk's
    rows is ``window[:, n : n + L - 1]``, so after none of them it is the
    state that came in).  Its three parts (:func:`conv_gates`,
    :func:`conv_filter`, :func:`conv_out`) one after another: a step whose
    rows are several groups of lanes side by side runs the first and the
    last once over all of them and the filter a group at a time."""
    gate_c, g = conv_gates(conv, y, dtype)
    c, window = conv_filter(conv, g, state, dtype)
    return conv_out(conv, gate_c, c, dtype), window


def state_after(window: jax.Array, rows_done: jax.Array, taps: int
                ) -> jax.Array:
    """The state a lane holds once ``rows_done`` [B] of its chunk's rows
    are real (the rest padding, or the lane idle: 0): rows ``rows_done ..
    rows_done + L - 2`` of ``window`` [B, L - 1 + C, d]."""
    with jax.named_scope("conv_state"):
        index = rows_done[:, None] + jnp.arange(taps - 1)[None, :]
        return jnp.take_along_axis(window, index[:, :, None], axis=1)
