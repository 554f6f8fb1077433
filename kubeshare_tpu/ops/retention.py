"""Power retention (power p = 2): attention whose weight is a decayed
SQUARE of the scaled dot product, never negative, with no softmax —

    w_ij = exp(A_i - A_j) * (q_i . k_j / sqrt(hd))^2        for j <= i
    o_i  = sum_j w_ij v_j / (sum_j w_ij + eps)

(``A`` the running sum of a per-row log gate ``a <= 0``, one a KV head) —
and the same numbers from a state that does not grow with the request,
because ``(q . k)^2 = phi(q) . phi(k)``:

    S_t = e^{a_t} S_{t-1} + phi(k_t) [v_t | 1]^T
    o_t = phi(q_t)^T S_t[:, :hd] / (phi(q_t)^T S_t[:, hd] + eps)

What a serving step needs of it, each in float32 (the state and every sum
that feeds it; the dot products of the unfolded rows are the served
dtype's, as in the other blocks):

- :func:`phi`, the feature map, laid out by DIAGONALS: ``phi(u)[o * hd + a]
  = c_o u_a u_{(a + o) mod hd}`` for o = 0 .. hd / 2.  Diagonal 0 holds the
  squares (c = 1), diagonals 1 .. hd/2 - 1 each unordered pair at that
  cyclic distance once (c = sqrt 2), diagonal hd/2 its hd/2 pairs in its
  first half (c = sqrt 2) and zeros in its second: hd (hd + 1) / 2 features
  (8,256 at hd 128) in (hd / 2 + 1) x hd columns (8,320, what the TPU's
  tiles pad 8,256 to) — a roll and a product a diagonal, no gather.
- :func:`tail_sums`: the weights of the UNFOLDED rows a lane still holds
  as keys and values (a window of the paged pool that starts at the lane's
  fold point), as a running SUM: numerator and denominator, no maximum.
- :func:`state_sums`: the state's share, ``phi(q)^T [S | z]`` decayed from
  the fold point to the query's row.
- :func:`fold_update`: ``[S | z] <- e^{A_e - A_F} [S | z] + sum_j e^{A_e -
  A_j} phi(k_j) [v_j | 1]^T`` over one key block of rows, e its last.
- :func:`retention_quadratic`: every row against every earlier row, the
  unpaged forward's and the tests' second path (no state, no ``phi``).

The state is stored TRANSPOSED, ``[h_kv, state_rows, phi_width]`` a lane and
layer: rows 0 .. hd - 1 the values' columns of S, row hd the sum of keys z,
and the rows up to ``state_rows`` = hd + 1 rounded up to 8 (136 at hd 128)
zeros nothing reads.  The wide axis is last (129 as the last axis would pad
to 256), the heads are outside the rows (what the batched products want),
and the rows are whole TPU tiles as stored: an array of 129 rows pads to 136
anyway, and the compiler then hands it back in another layout than it was
given in — a copy of every state, 5 GB, each dispatch
(``tests/test_chip_compile.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6  # the quotient's: o = num / (den + EPS)
_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST  # float32 products in float32


def state_rows(head_dim: int) -> int:
    """Rows of a stored state: hd values' columns and the sum of keys,
    rounded up to whole tiles of 8."""
    return -(-(head_dim + 1) // 8) * 8


def phi_width(head_dim: int) -> int:
    """Columns of :func:`phi`: ``(hd / 2 + 1) x hd``."""
    return (head_dim // 2 + 1) * head_dim


@jax.named_scope("phi")
def phi(u):
    """``u`` [..., hd] -> float32 [..., phi_width(hd)] with ``phi(q) .
    phi(k) == (q . k)^2`` (module docstring: by diagonals)."""
    hd = u.shape[-1]
    half = hd // 2
    u = u.astype(_F32)
    twice = jnp.concatenate([u, u], axis=-1)
    root2 = jnp.sqrt(_F32(2.0))
    parts = [u * u]
    for o in range(1, half):
        parts.append(root2 * u * twice[..., o:o + hd])
    last = jnp.where(jnp.arange(hd) < half, root2, 0.0).astype(_F32)
    parts.append(last * u * twice[..., half:half + hd])
    return jnp.concatenate(parts, axis=-1)


def _grouped(q, h_kv: int):
    """[B, H, C, hd] -> [B, h_kv, g * C, hd]: a KV head's query heads side
    by side (they share its keys, values, gate and state)."""
    b, h, c, hd = q.shape
    return q.reshape(b, h_kv, (h // h_kv) * c, hd)


@jax.named_scope("retention_tail")
def tail_sums(q, k_win, v_win, cum_q, cum_win, q_row, dtype):
    """The unfolded rows' share of a query's sums.

    ``q`` [B, H, C, hd]; ``k_win`` / ``v_win`` [B, h_kv, W, hd] a window of
    W rows that starts at each lane's fold point; ``cum_win`` [B, W, h_kv]
    the gate's running log over the window (row r: the sum of ``a`` over
    window rows 0 .. r); ``cum_q`` [B, C, h_kv] the same at each query's
    own row, which is window row ``q_row`` [B, C] — a query sees window
    rows ``<= q_row`` (a dead query: -1, none).  Returns (num [B, H, C, hd],
    den [B, H, C]) float32: ``sum_j w_ij v_j`` and ``sum_j w_ij``."""
    b, h, c, hd = q.shape
    h_kv, w = k_win.shape[1], k_win.shape[2]
    g = h // h_kv
    qg = q.reshape(b, h_kv, g, c, hd)
    scores = jnp.einsum("bhgcd,bhwd->bhgcw", qg, k_win,
                        preferred_element_type=_F32) * hd ** -0.5
    seen = jnp.arange(w)[None, None, :] <= q_row[:, :, None]  # [B, C, W]
    # A_i - A_j, [B, h_kv, C, W]; never positive where the row is seen
    log_decay = (cum_q.transpose(0, 2, 1)[:, :, :, None]
                 - cum_win.transpose(0, 2, 1)[:, :, None, :])
    decay = jnp.exp(jnp.where(seen[:, None], log_decay, -jnp.inf))
    weights = decay[:, :, None] * scores * scores  # [B, h_kv, g, C, W]
    num = jnp.einsum("bhgcw,bhwd->bhgcd", weights.astype(dtype), v_win,
                     preferred_element_type=_F32)
    den = jnp.sum(weights, axis=-1)
    return num.reshape(b, h, c, hd), den.reshape(b, h, c)


@jax.named_scope("retention_state")
def state_sums(q, state, cum_q, has_state):
    """The state's share of a query's sums.

    ``q`` [B, H, C, hd]; ``state`` [B, h_kv, state_rows, phi_width] float32,
    each lane's ``[S | z]`` transposed, as of its fold point; ``cum_q``
    [B, C, h_kv] the gate's running log from the fold point to each query's
    row; ``has_state`` [B]: a lane that has folded nothing reads as zero,
    whatever its slot holds.  Scaled by 1 / hd, as the squared scores of
    :func:`tail_sums` are.  Returns (num [B, H, C, hd], den [B, H, C])."""
    b, h, c, hd = q.shape
    h_kv = state.shape[1]
    g = h // h_kv
    decay = jnp.exp(cum_q).transpose(0, 2, 1)  # [B, h_kv, C]
    decay = jnp.where(has_state[:, None, None], decay / hd, 0.0)

    def one_head(i):
        """A KV head at a time: ``phi`` of a chunk's rows is 85 MB a head
        at 5 x 512 rows of hd 128."""
        q_h = jax.lax.dynamic_index_in_dim(_grouped(q, h_kv), i, 1, False)
        s_h = jax.lax.dynamic_index_in_dim(state, i, 1, False)
        return jnp.einsum("brd,bcd->brc", phi(q_h), s_h, precision=_EXACT)

    if c == 1:
        # a decode step's g rows a KV head: nothing for the matrix unit
        # to tile; the products and their sum over phi's columns in
        # float32 on the vector unit, each state read once as it lies
        phi_q = phi(_grouped(q, h_kv))  # [B, h_kv, g, D]
        sums = jnp.sum(phi_q[:, :, :, None] * state[:, :, None], axis=-1)
    else:
        sums = jax.lax.map(one_head, jnp.arange(h_kv)).transpose(1, 0, 2, 3)
    sums = sums[..., :hd + 1].reshape(b, h_kv, g, c, hd + 1) \
        * decay[:, :, None, :, None]
    return (sums[..., :hd].reshape(b, h, c, hd),
            sums[..., hd].reshape(b, h, c))


def retention_output(tail, state, dtype):
    """``(num, den)`` of the tail and of the state -> o [B, H, C, hd]."""
    num, den = tail[0] + state[0], tail[1] + state[1]
    return (num / (den[..., None] + EPS)).astype(dtype)


@jax.named_scope("retention_fold")
def fold_update(old, k_rows, v_rows, a_rows, had_state):
    """One lane's state over one more key block of rows.

    ``old`` [h_kv, state_rows, phi_width] float32 (as of the rows before);
    ``k_rows`` / ``v_rows`` [h_kv, J, hd]; ``a_rows`` [J, h_kv] the rows'
    log gates; ``had_state``: whether ``old`` holds anything (else it is
    taken as zero, whatever the slot holds).  Returns the state as of the
    block's last row e: ``e^{A_e - A_F} old + sum_j e^{A_e - A_j} [v_j |
    1]^T phi(k_j)``."""
    cum = jnp.cumsum(a_rows.astype(_F32), axis=0)  # [J, h_kv]
    total = cum[-1]  # [h_kv]
    weight = jnp.exp(total[None, :] - cum).T  # [h_kv, J], <= 1
    spare = old.shape[1] - v_rows.shape[-1] - 1
    v1 = jnp.concatenate(
        [v_rows.astype(_F32), jnp.ones(v_rows.shape[:2] + (1,), _F32),
         jnp.zeros(v_rows.shape[:2] + (spare,), _F32)], axis=-1)

    def one_head(head):
        """A KV head at a time: ``phi`` of a key block's rows is 17 MB a
        head at 512 rows of hd 128, and the heads take turns."""
        values, keys = head
        return jnp.einsum("jc,jd->cd", values, phi(keys), precision=_EXACT)

    new = jax.lax.map(one_head, (v1 * weight[:, :, None], k_rows))
    keep = jnp.where(had_state, jnp.exp(total), 0.0)
    return keep[:, None, None] * old + new


def retention_quadratic(q, k, v, a, dtype):
    """Every row against every earlier row: ``q`` [B, H, S, hd], ``k`` /
    ``v`` [B, h_kv, S, hd], ``a`` [B, S, h_kv] float32 log gates -> o [B,
    H, S, hd].  :func:`tail_sums` over the whole sequence as one window,
    and no state."""
    s = q.shape[2]
    cum = jnp.cumsum(a.astype(_F32), axis=1)
    rows = jnp.broadcast_to(jnp.arange(s)[None, :], (q.shape[0], s))
    num, den = tail_sums(q, k, v, cum, cum, rows, dtype)
    return (num / (den[..., None] + EPS)).astype(dtype)
