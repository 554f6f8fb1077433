"""The plain reference of ``configs/sdar-30b-a3b-chat.json``: the first
pipeline stage of SDAR-30B-A3B-Chat and its generation by diffusion over
blocks, written from the equations in straightforward ``jax.numpy``,
float32, matmul precision ``highest`` — no paging, no batching of requests,
no grouping of rows by expert.  Nothing is imported from the program.

d = ``d_model``, H = ``n_heads`` of width hd = ``head_width``, K =
``n_kv_heads``, all linear maps without bias, RMSNorm(x) = x . rsqrt(mean
x^2 + eps) . g, B = ``diffusion_block``:

    layer:   a = RMSNorm(x)
             q = RMSNorm_hd(reshape(a Wq, H x hd));  k = RMSNorm_hd(reshape(a Wk, K x hd));  v = reshape(a Wv, K x hd)
             q, k = rope(q, k): halves (x[:hd/2], x[hd/2:]) turned by p . theta^(-2i / hd), p the absolute index
             score(i, j) = q_i . k_j / sqrt(hd), each kv head serving H / K query heads,
                           row i sees row j iff  j // B <= i // B           (block-causal)
             h = x + concat_heads(softmax_j(score) v) Wo
             y = RMSNorm(h);  p = softmax_f32(y Wr);  T = the top_k of p;  w_e = p_e / sum_{e' in T} p_e'
             x' = h + sum_{e in T} w_e . (silu(y Wg_e) * (y Wu_e)) Wd_e
    model:   embed -> layers -> RMSNorm -> untied head
    generation (greedy), prompt of P tokens:
      rows 0 .. (P // B) B - 1 are prefilled under the mask and their K/V kept
      each following block: known = the prompt's tail in it; the rest masked (their input is the mask token's embedding)
        repeat: one pass over the block's B rows, its K/V NOT kept; at each masked row i t_i = argmax logits_i,
                c_i = softmax(logits_i)[t_i]; commit the n_s masked rows of highest c (ties: the lowest index)
        when no row is masked: one more pass over the finished block, its K/V kept

``generate`` is that loop.  **A row past the request's budget** (the last
block of a request whose ``max_new`` ends inside it) stays masked and is
never committed: the configuration's departure from the published loop, which
generates whole blocks and cuts the surplus off — the harness hands this file
the served tokens alone, so the surplus rows' picks could not be replayed.

``served_gaps`` **replays the schedule, teacher-forced**.  Under the
block-causal mask the K/V a finished block leaves depend on its own and the
earlier blocks' FINAL tokens only, so one pass over the prompt and the served
tokens gives every block's kept K/V at once, and pass s of every block can
then be computed side by side: block g's rows attend the kept K/V of the rows
before it and one another.  At pass s the reference commits, in each block,
the ``n_s`` masked rows where the SERVED token's probability is highest
(that is how it tells which rows the program committed at that pass: a token
served there is, up to a tie, the argmax there), and a row's gap is its best
logit less its logit of the served token AT THAT PASS.  An order decided the
other way near a tie costs at most that block's rows.

That replay alone cannot tell the ORDER: it tells which row was committed by
the served token's probability, the order most favourable to whatever the
program did, so a program on another schedule reads only as much worse as
its picks depended on the rows it committed too early or too late.  So the
same served tokens are also read under the other schedules the reference can
form (``SCHEDULES``: a block's rows in index order, in reverse, all in one
pass; each row's gap in the context THAT rule gives it), and ``order_gap`` is
the stated schedule's mean gap less the least of theirs.  Precision moves
both means alike, so the difference holds the schedule alone: tokens the
stated schedule generated fit it best and read below zero; tokens another
rule generated fit that rule better and read above.

Every expert is run on every row and the rows that did not choose it are
weighted 0 (one ``lax.scan`` over the experts).  It is handed the benchmark's
own seeded bf16 weights, which stay on the device (8.7 GB at the cell's size),
and upcasts them a piece at a time: one attention, one expert — never a
layer; the control lowers them the same way.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import CONTROL, _LOW, _f32, _fp8  # noqa: F401

SIZES = ("d_model", "n_heads", "n_kv_heads", "head_width", "rope_theta",
         "norm_eps", "n_routed_experts", "router_top_k",
         "routed_scaling_factor", "diffusion_block")
PAD_TO = 4096  # sequences are padded to a multiple: one shape compiles, not one a length
PASS_PAD = 1032  # and so are the rows of a pass over every generated block (at most 1028 of a request of 1024 tokens)
QUERY_BLOCK = 512  # attention runs in query blocks of this many rows


def _sizes(tc: Dict):
    return tuple((k, tc.get(k, 1.0 if k == "routed_scaling_factor" else 0))
                 for k in SIZES)


def transfer_counts(tc: Dict) -> Tuple[int, ...]:
    """Rows a block commits at each of its denoising passes: B spread over
    the steps, the remainder to the first."""
    base, extra = divmod(tc["diffusion_block"], tc["diffusion_steps"])
    return tuple(base + (i < extra) for i in range(tc["diffusion_steps"]))


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(scale)


def _rope(x, positions, theta):
    """x [T, heads, hd]; the pairs (x[..., :hd/2], x[..., hd/2:])."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _qkv(x, attn, norm, positions, s, act):
    """The three projections of RMSNorm(x), x [T, d]: q [T, H, hd] and
    k [T, K, hd] normed a head and turned, v [T, K, hd]."""
    eps, theta = s["norm_eps"], s["rope_theta"]
    y = act(_rms_norm(x, norm["scale"], eps))
    q = jnp.einsum("td,dhk->thk", y, _f32(attn["wq"]))
    k = jnp.einsum("td,dhk->thk", y, _f32(attn["wk"]))
    v = jnp.einsum("td,dhk->thk", y, _f32(attn["wv"]))
    q = _rms_norm(q, attn["q_norm"]["scale"], eps)
    k = _rms_norm(k, attn["k_norm"]["scale"], eps)
    return _rope(q, positions, theta), _rope(k, positions, theta), v


def _attention(q, k, v, seen_of):
    """Grouped-query attention of q [Q, H, hd] over keys k, v [S, K, hd];
    ``seen_of(start, rows)`` gives the [rows, S] mask of a query block."""
    n, h, hd = q.shape
    h_kv = k.shape[1]
    out = []
    for start in range(0, n, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        rows = qb.shape[0]
        qb = qb.reshape(rows, h_kv, h // h_kv, hd)
        scores = jnp.einsum("qkgd,skd->kgqs", qb, k) * hd ** -0.5
        scores = jnp.where(seen_of(start, rows)[None, None], scores, -jnp.inf)
        ob = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, -1), v)
        out.append(ob.reshape(rows, h, hd))
    return jnp.concatenate(out, 0)


@partial(jax.jit, static_argnums=(5, 6))
def _attend_own(x, attn, norm, positions, length, sizes, fp8_inputs=False):
    """x + attention(RMSNorm(x)) of a whole sequence under the block-causal
    mask, x [T, d] of which the first ``length`` rows are real (a sequence
    that ends inside a block sees no padding there); also the layer's k and
    v, float32."""
    s = dict(sizes)
    act = _fp8 if fp8_inputs else (lambda a: a)
    b = max(s["diffusion_block"], 1)
    with jax.default_matmul_precision("highest"):
        q, k, v = _qkv(x, attn, norm, positions, s, act)
        if not s["diffusion_block"]:
            reach = positions
        else:
            reach = positions // b * b + (b - 1)

        def seen_of(start, rows):
            return (positions[None, :] <= reach[start:start + rows, None]) \
                & (positions[None, :] < jnp.maximum(length, 1))

        o = act(_attention(q, k, v, seen_of))
        return x + jnp.einsum("thk,hkd->td", o, _f32(attn["wo"])), k, v


@partial(jax.jit, static_argnums=(7, 8))
def _attend_kept(x, attn, norm, positions, kept_k, kept_v, kept_positions,
                 sizes, fp8_inputs=False):
    """x + attention(RMSNorm(x)) of one pass's rows, x [Q, d] at
    ``positions``: a row sees the kept K/V of every row BEFORE its block
    and this pass's own K/V of its block's rows."""
    s = dict(sizes)
    act = _fp8 if fp8_inputs else (lambda a: a)
    b = s["diffusion_block"]
    with jax.default_matmul_precision("highest"):
        q, k, v = _qkv(x, attn, norm, positions, s, act)
        base = positions // b * b

        def seen_of(start, rows):
            mine = base[start:start + rows, None]
            return jnp.concatenate(
                [kept_positions[None, :] < mine, base[None, :] == mine], 1)

        o = act(_attention(q, jnp.concatenate([kept_k, k]),
                           jnp.concatenate([kept_v, v]), seen_of))
        return x + jnp.einsum("thk,hkd->td", o, _f32(attn["wo"]))


def router_weights(y, router, sizes):
    """[T, experts]: w_e where the row chose e, else 0."""
    s = dict(sizes)
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(y @ _f32(router), -1)
    _, chosen = jax.lax.top_k(probs, s["router_top_k"])
    picked = jax.nn.one_hot(chosen, probs.shape[-1]).sum(1) * probs
    return s["routed_scaling_factor"] * picked \
        / picked.sum(-1, keepdims=True)


@partial(jax.jit, static_argnums=(3, 4))
def _experts(x, norm, moe: Dict, sizes, low: str = ""):
    """x + sum_{e in T} w_e . FFN_e(RMSNorm(x)), x [T, d]: every expert
    over every row.  ``low`` lowers the router and each expert's matrices,
    a tensor each, and the activation operands."""
    lower = _LOW[low] if low else (lambda w: w)
    act = _fp8 if low == "fp8" else (lambda a: a)
    y = act(_rms_norm(x, norm["scale"], dict(sizes)["norm_eps"]))
    weights = router_weights(y, lower(moe["router"]), sizes)

    def one(out, expert):
        w_gate, w_up, w_down, weight = expert
        with jax.default_matmul_precision("highest"):
            hidden = jax.nn.silu(y @ _f32(lower(w_gate))) \
                * (y @ _f32(lower(w_up)))
            result = act(hidden) @ _f32(lower(w_down))
        return out + weight[:, None] * result, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (moe["w_gate"], moe["w_up"], moe["w_down"], weights.T))
    return x + out


@partial(jax.jit, static_argnums=(1,))
def _lower_attention(attn: Dict, kind: str) -> Dict:
    return {k: (v if "norm" in k else _LOW[kind](v)) for k, v in attn.items()}


@partial(jax.jit, static_argnums=(1,))
def lower_precision(layer: Dict, kind: str = CONTROL) -> Dict:
    """One whole layer in the lower precision, for a program that is to
    serve it (the twin's control): every matrix a tensor, each expert's
    too; norm scales stay."""
    low, moe = _LOW[kind], layer["moe"]
    return {**layer, "attn": _lower_attention(layer["attn"], kind),
            "moe": {**moe, "router": low(moe["router"]),
                    **{k: jax.vmap(low)(moe[k])
                       for k in ("w_gate", "w_up", "w_down")}}}


@partial(jax.jit, static_argnums=(3,))
def _embed(embed, tokens, masked, mask_token):
    """A masked row's input is the mask token's embedding, whatever id the
    row holds: masked-ness is position state."""
    return _f32(embed[jnp.where(masked, mask_token, tokens)])


@partial(jax.jit, static_argnums=(4, 5))
def _head_stats(x, scale, lm_head, at, eps, fp8_inputs=False):
    """Of each row's logits: the best, its index, log sum exp, and the
    logit of the id ``at`` gives the row.  The logits stay here."""
    act = _fp8 if fp8_inputs else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        logits = act(_rms_norm(x, scale, eps)) @ _f32(lm_head)
    return (logits.max(-1), logits.argmax(-1),
            jax.nn.logsumexp(logits, -1),
            jnp.take_along_axis(logits, at[:, None], -1)[:, 0])


@partial(jax.jit, static_argnums=(4, 5))
def _head(x, rows, scale, lm_head, eps, fp8_inputs=False):
    act = _fp8 if fp8_inputs else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        return act(_rms_norm(x[rows], scale, eps)) @ _f32(lm_head)


def _padded(n: int, to: int) -> int:
    """``n`` up to a multiple of ``to``; a short one (a test's) to a
    sixteenth of it."""
    to = to // 16 if n <= to // 16 else to
    return max(-(-n // to), 1) * to


class _Model:
    """The weights under one precision: the two passes the reference makes."""

    def __init__(self, params: Dict, tc: Dict, low: str = "") -> None:
        self.params, self.tc, self.low = params, tc, low
        self.sizes, self.fp8 = _sizes(tc), low == "fp8"
        self.lm_head = _LOW[low](params["lm_head"]) if low \
            else params["lm_head"]

    def _attn(self, layer):
        return _lower_attention(layer["attn"], self.low) if self.low \
            else layer["attn"]

    def whole(self, tokens: np.ndarray, masked: np.ndarray):
        """The pass over a whole sequence under the block-causal mask: the
        final hidden states [T_padded, d] and each layer's (k, v), float32.
        Pad rows come after every real row, in blocks of their own: no real
        row sees them."""
        n = _padded(len(tokens), PAD_TO)
        toks, mask = np.zeros((n,), np.int32), np.zeros((n,), bool)
        toks[:len(tokens)], mask[:len(tokens)] = tokens, masked
        positions = jnp.arange(n, dtype=jnp.int32)
        x = _embed(self.params["embed"], jnp.asarray(toks), jnp.asarray(mask),
                   self.tc.get("mask_token", 0))
        kept = []
        for layer in self.params["layers"]:
            x, k, v = _attend_own(x, self._attn(layer), layer["norm1"],
                                  positions, len(tokens), self.sizes,
                                  self.fp8)
            x = _experts(x, layer["norm2"], layer["moe"], self.sizes,
                         self.low)
            kept.append((k, v))
        return x, kept

    def blocks(self, tokens, masked, positions, kept):
        """One pass over the rows at ``positions`` (whole blocks), each
        block over the kept K/V of the rows before it: the final hidden
        states [Q, d]."""
        kept_positions = jnp.arange(kept[0][0].shape[0], dtype=jnp.int32)
        x = _embed(self.params["embed"], jnp.asarray(tokens),
                   jnp.asarray(masked), self.tc.get("mask_token", 0))
        positions = jnp.asarray(positions, jnp.int32)
        for layer, (k, v) in zip(self.params["layers"], kept):
            x = _attend_kept(x, self._attn(layer), layer["norm1"], positions,
                             k, v, kept_positions, self.sizes, self.fp8)
            x = _experts(x, layer["norm2"], layer["moe"], self.sizes,
                         self.low)
        return x

    def stats(self, x, at: np.ndarray):
        best, index, lse, there = _head_stats(
            x, self.params["final_norm"]["scale"], self.lm_head,
            jnp.asarray(at, jnp.int32), self.tc["norm_eps"], self.fp8)
        return (np.asarray(best), np.asarray(index), np.asarray(lse),
                np.asarray(there))


def reference_logits(params: Dict, tc: Dict, tokens: np.ndarray,
                     rows: np.ndarray, low: str = "",
                     masked: np.ndarray = None) -> np.ndarray:
    """float32 logits [len(rows), vocab] of one pass over ``tokens`` under
    the configuration's mask, at the positions ``rows``; the rows ``masked``
    says so take the mask token's embedding."""
    tokens = np.asarray(tokens, np.int32)
    masked = np.zeros(len(tokens), bool) if masked is None else masked
    model = _Model(params, tc, low)
    x, _ = model.whole(tokens, masked)
    width = _padded(len(rows), 1024)
    padded_rows = np.zeros((width,), np.int32)
    padded_rows[:len(rows)] = rows
    logits = _head(x, jnp.asarray(padded_rows), params["final_norm"]["scale"],
                   model.lm_head, tc["norm_eps"], low == "fp8")
    return np.asarray(logits[:len(rows)])


def _commit_order(score: np.ndarray, may: np.ndarray, count: int):
    """The ``count`` rows of highest ``score`` among those ``may`` says,
    ties to the lowest index."""
    order = sorted(np.flatnonzero(may), key=lambda i: (-score[i], i))
    return order[:count]


def generate(params: Dict, tc: Dict, prompt: np.ndarray, max_new: int,
             schedule: str = "") -> List[int]:
    """The generation loop itself, one block after another (for a test's
    sizes: every finished block is followed by a pass over the whole
    sequence so far, which is what leaves its K/V).  A ``schedule`` of
    ``SCHEDULES`` commits by that rule instead of the most confident rows
    first: a program that has to FAIL the comparison."""
    b, counts = tc["diffusion_block"], transfer_counts(tc)
    model = _Model(params, tc)
    prompt = np.asarray(prompt, np.int32)
    p, end = len(prompt), len(prompt) + max_new
    tokens = np.zeros((-(-end // b) * b,), np.int32)
    tokens[:p] = prompt
    masked = np.arange(len(tokens)) >= p
    index = np.arange(b, dtype=np.float64)
    for base in range(p // b * b, end, b):
        rows = np.arange(base, base + b)
        open_rows = masked[rows] & (rows < end)
        _, kept = model.whole(tokens[:base], masked[:base])
        for count in counts:
            if not open_rows.any():
                break
            x = model.blocks(tokens[rows], masked[rows], rows, kept)
            best, picked, lse, _ = model.stats(x, np.zeros(b, np.int32))
            score = {"": np.exp(best - lse), "index": -index,
                     "reverse": index, "at_once": index}[schedule]
            for i in _commit_order(score, open_rows,
                                   b if schedule == "at_once" else count):
                tokens[base + i], masked[base + i] = picked[i], False
                open_rows[i] = False
    return [int(t) for t in tokens[p:end]]


SCHEDULES = ("index", "reverse", "at_once")


def _other_pattern(schedule: str, offset: int, b: int) -> int:
    """Which of its block's other rows (a bit an offset) are committed when
    ``schedule`` picks the row at ``offset``: the rows before it in index
    order, the rows after it in reverse order, none where the whole block
    is committed in one pass.  (One row a pass, as the configuration and
    its twin run: ``diffusion_steps`` = ``diffusion_block``.)"""
    return {"index": (1 << offset) - 1,
            "reverse": ((1 << b) - 1) & ~((2 << offset) - 1),
            "at_once": 0}[schedule]


class _Replay:
    """One request laid out for the passes over its generated blocks: the
    kept K/V of every finished block (one pass over the prompt and the
    served tokens) and, of the rows from the first generated block on,
    the tokens, which of them were generated, and which are past the
    request's budget."""

    def __init__(self, params: Dict, tc: Dict, prompt, served,
                 low: str = "") -> None:
        b = self.b = tc["diffusion_block"]
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        p, end = len(prompt), len(prompt) + len(served)
        first = p // b * b
        total = -(-end // b) * b
        final = np.zeros((total,), np.int32)
        final[:p], final[p:end] = prompt, served
        dead = np.arange(total) >= end  # masked for good
        self.model = _Model(params, tc, low)
        self.kept = self.model.whole(final, dead)[1]
        rows = np.arange(first, total)
        self.rows = len(rows)
        width = _padded(len(rows), PASS_PAD)
        self.positions = np.concatenate(
            [rows, total + np.arange(width - len(rows))]).astype(np.int32)
        self.tokens = np.zeros((width,), np.int32)
        self.tokens[:len(rows)] = final[rows]
        self.unknown = np.zeros((width,), bool)  # not of the prompt
        self.unknown[:len(rows)] = rows >= p
        self.served = self.unknown & (self.positions < end)
        self.generated = slice(p - first, end - first)

    def stats(self, masked: np.ndarray, at: np.ndarray = None):
        """One pass with the rows ``masked`` says still unknown: of every
        row's logits the best, its index, log sum exp and the logit of the
        row's served token (or of the id ``at`` gives it)."""
        x = self.model.blocks(self.tokens, masked, self.positions, self.kept)
        return self.model.stats(x, self.tokens if at is None else at), x

    def pattern(self, committed: int) -> np.ndarray:
        """The rows masked where every block's served rows at the offsets
        ``committed`` has a bit for hold their tokens."""
        offsets = np.arange(len(self.tokens)) % self.b
        return self.unknown & ~(self.served
                                & ((committed >> offsets) & 1).astype(bool))


def _replay(params: Dict, tc: Dict, prompt: np.ndarray,
            served: Sequence[int], control: str = "",
            schedules: Sequence[str] = ()) -> Dict[str, np.ndarray]:
    """The teacher-forced replay (see the top of this file).  ``"stated"``:
    each served token's gap at the pass the reference commits its row —
    with a ``control``, at that pass, the gap of the token the lower
    precision puts first there.  Each of ``schedules``: the served tokens'
    gaps where the block's rows are committed by that rule instead."""
    b, counts = tc["diffusion_block"], transfer_counts(tc)
    replay = _Replay(params, tc, prompt, served)
    low = _Replay(params, tc, prompt, served, control) if control else None
    n = replay.rows
    masked, open_rows = replay.unknown.copy(), replay.served.copy()
    gaps = np.full((n,), np.nan)
    first_pass = None
    for count in counts:
        if not open_rows.any():
            break
        (best, _, lse, there), x = replay.stats(masked)
        if first_pass is None:
            first_pass = best - there
        if control:
            picked = low.stats(masked)[0][1]
            there_low = replay.model.stats(x, picked)[3]
        for start in range(0, n, b):
            block = slice(start, start + b)
            for i in _commit_order(there[block] - lse[block],
                                   open_rows[block], count):
                row = start + i
                gaps[row] = best[row] - (there_low if control else there)[row]
                masked[row] = open_rows[row] = False
    assert not open_rows.any(), "the schedule left rows masked"
    out = {"stated": gaps[replay.generated]}
    offsets = np.arange(n) % b
    passes = {0: first_pass}  # committed pattern -> every row's gap under it
    for schedule in schedules:
        other = np.full((n,), np.nan)
        for offset in range(b):
            committed = _other_pattern(schedule, offset, b)
            if committed not in passes:
                (best, _, _, there), _ = replay.stats(
                    replay.pattern(committed))
                passes[committed] = best - there
            at = offsets == offset
            other[at] = passes[committed][:n][at]
        out[schedule] = other[replay.generated]
    return out


class Gaps(np.ndarray):
    """A request's gaps under the stated schedule, and ``others``: the same
    served tokens' gaps under each of ``SCHEDULES`` (the harness hands
    ``summarize`` what ``served_gaps`` returned, nothing else)."""

    others: Dict[str, np.ndarray] = {}


def served_gaps(params: Dict, tc: Dict, prompt: np.ndarray,
                served: Sequence[int]) -> np.ndarray:
    """For one request: how far each served token's reference logit lies
    below the reference's best, at the pass that committed it (0 where they
    agree); as ``.others``, the same under the other schedules."""
    found = _replay(params, tc, prompt, served, schedules=SCHEDULES)
    gaps = found.pop("stated").view(Gaps)
    gaps.others = found
    return gaps


def control_gaps(params: Dict, tc: Dict, prompt: np.ndarray,
                 served: Sequence[int], kind: str = CONTROL) -> np.ndarray:
    """Along the same replay, the gap of the token the lower precision puts
    first at each row's pass."""
    return _replay(params, tc, prompt, served, control=kind)["stated"]


def summarize(gaps: List[np.ndarray]) -> Dict[str, float]:
    """The numbers compared.  ``mean_gap``: over all served tokens of the
    sample, under the stated schedule.  ``order_gap``: that mean less the
    least mean the same tokens read under another schedule (``least_other``
    names it) — below zero where the served tokens fit the stated schedule
    best, above where they fit another one better."""
    flat = np.concatenate(gaps)
    out = {"widest_gap": float(flat.max()), "mean_gap": float(flat.mean()),
           "tokens": int(flat.size),
           "off_best": int(np.count_nonzero(flat > 0))}
    others = {name: float(np.concatenate([g.others[name]
                                          for g in gaps]).mean())
              for name in SCHEDULES
              if all(isinstance(g, Gaps) for g in gaps)}
    if others:
        least = min(others, key=others.get)
        out.update(others, least_other=least,
                   order_gap=out["mean_gap"] - others[least])
    return out
