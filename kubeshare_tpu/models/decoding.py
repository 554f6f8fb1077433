"""Autoregressive decoding with a KV cache for the flagship Transformer.

Serving-shaped workload path (the training side lives in parallel/train):
prefill populates a static-shape KV cache, then a ``lax.scan`` decode loop
generates tokens one at a time — everything static-shaped and jit-compiled
once, the way TPU decoding must be (no growing arrays, no Python loop).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.rope import apply_rope
from .transformer import (TransformerConfig, _rms_norm, attend_key_blocks,
                          latent_attend, latent_layers, latent_qkv)


def _check_moe_decodable(config: TransformerConfig) -> None:
    """The routing contract every cached path shares (decode step and
    both prefills)."""
    if config.moe_routing == "experts_choose":
        raise ValueError(
            "expert-choice routing cannot be replayed token-by-token (an "
            "expert's choices depend on the whole sequence); decode "
            "requires moe_routing='tokens_choose'"
        )
    if config.moe_routing != "tokens_choose":
        raise ValueError(f"unknown moe_routing {config.moe_routing!r}")


def _check_cache_headroom(cache: Dict, max_new_tokens: int,
                          prefill_length: Optional[int] = None) -> None:
    """The loud failure both cached decode splits share: past capacity,
    dynamic_update_slice clamps and silently overwrites the last cache
    slot.

    Outside jit the concrete cache length is checked directly.  Under jit
    the length is a tracer and the full bound cannot be evaluated at trace
    time — callers jitting a ``*_with_cache`` continuation (the headline
    serving pattern, examples/serve_fractional.py) must pass their static
    ``prefill_length`` so the real bound is enforced; without it only the
    weaker ``max_new_tokens <= capacity`` check applies and a continuation
    from a nearly-full cache can silently overwrite the last slot
    (ADVICE r4 medium)."""
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    capacity = cache["k"].shape[3]
    length = cache["length"]
    if prefill_length is not None and prefill_length + max_new_tokens > capacity:
        raise ValueError(
            f"prefill_length {prefill_length} + max_new_tokens "
            f"{max_new_tokens} exceeds the cache capacity {capacity}"
        )
    # the concrete-length check applies INDEPENDENTLY of prefill_length:
    # outside jit the cache's real length is authoritative (a caller
    # passing an understated prefill_length must still fail loudly)
    if not isinstance(length, jax.core.Tracer):
        if int(length) + max_new_tokens > capacity:
            raise ValueError(
                f"cache length {int(length)} + max_new_tokens "
                f"{max_new_tokens} exceeds the cache capacity {capacity}"
            )
    elif prefill_length is None and max_new_tokens > capacity:
        raise ValueError(
            f"max_new_tokens {max_new_tokens} exceeds the cache "
            f"capacity {capacity}"
        )


def _check_prompt_fits(config: TransformerConfig, prompt_len: int) -> None:
    if prompt_len > config.max_seq_len:
        # dynamic_update_slice would silently clamp at the window edge
        raise ValueError(
            f"prompt length {prompt_len} exceeds max_seq_len "
            f"{config.max_seq_len}"
        )


def init_kv_cache(config: TransformerConfig, batch: int) -> Dict:
    """Static [layers x batch x kv_heads x max_seq x head_dim] cache.

    Under GQA (``n_kv_heads < n_heads``) the cache — decode's dominant
    HBM cost — shrinks by the query-group factor.  A latent block
    caches one headless row an attention sub-layer instead: ``k`` its
    latent values, ``v`` its rotary key."""
    if config.block in ("gqa_moe", "retention"):
        raise ValueError(
            f"block {config.block!r} has no dense-cache decoder: it is "
            f"served from the paged pool (serving/paged.py)")
    if config.latent:
        # the head axis stays (1): every cached path reads capacity there
        shape = (config.attn_sublayers, batch, 1, config.max_seq_len)
        return {
            "k": jnp.zeros((*shape, config.kv_lora_rank), config.dtype),
            "v": jnp.zeros((*shape, config.qk_rope_head_dim), config.dtype),
            "length": jnp.zeros((), jnp.int32),
        }
    shape = (batch, config.kv_heads, config.max_seq_len, config.head_dim)
    return {
        "k": jnp.zeros((config.n_layers, *shape), config.dtype),
        "v": jnp.zeros((config.n_layers, *shape), config.dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def _attend_cached(q, cache_k, cache_v, q_positions, window=None,
                   scale=None):
    """q: [b,h,Cq,d] against cache [b,h_kv,S,d]; per-query causal band
    (``scale``: what the scores are multiplied by, None ``d ** -0.5``).

    ``q_positions`` are the queries' global positions: query i sees
    cache slots ``k_pos <= q_positions[i]`` (and, with a window, within
    ``q_pos - k_pos < window`` — the same band transformer_apply's dense
    mask keeps).  Cq = 1 is the decode step; Cq > 1 is a prefill chunk.
    Shape [Cq] shares positions across the batch (the dense cache, whose
    rows advance in lockstep); shape [b, Cq] gives every batch row its
    OWN positions — the paged serving pool, where each slot sits at its
    own length (serving/paged.py).

    GQA: when h > h_kv the query heads are grouped over the shared KV
    heads ([b, h_kv, g, Cq, d] x [b, h_kv, S, d]) — no KV repetition is
    materialized, so the einsum reads each cached key/value once.
    """
    b, h, cq, d = q.shape
    h_kv = cache_k.shape[1]
    group = h // h_kv
    scale = d ** -0.5 if scale is None else scale
    qg = q.reshape(b, h_kv, group, cq, d)
    scores = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, cache_k).astype(jnp.float32) * scale
    k_pos = jnp.arange(cache_k.shape[2])
    if q_positions.ndim == 1:
        valid = k_pos[None, :] <= q_positions[:, None]  # [Cq, S]
        if window is not None:
            valid = valid & (q_positions[:, None] - k_pos[None, :] < window)
        valid = valid[None, None, None]  # -> [1,1,1,Cq,S]
    else:
        valid = k_pos[None, None, :] <= q_positions[:, :, None]  # [b, Cq, S]
        if window is not None:
            valid = valid & (
                q_positions[:, :, None] - k_pos[None, None, :] < window)
        valid = valid[:, None, None]  # -> [b,1,1,Cq,S]
    scores = jnp.where(valid, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(cache_v.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, cache_v)
    return out.reshape(b, h, cq, d)


def _attend_blocks(q, view_block, block_rows: int, kv_heads: int,
                   q_positions, window=None, scale=None):
    """:func:`_attend_cached` over a view that is handed over a block of
    K = ``block_rows`` rows at a time: ``view_block(i)`` gives rows
    ``[i * K, (i + 1) * K)`` of every lane's view as (k, v), each
    [b, ``kv_heads``, K, d]; ``q_positions`` [b, Cq] are per lane.  The
    blocks are attended through :func:`attend_key_blocks`, only as far as
    the furthest query reaches, so a step costs what its lanes hold.
    The query heads are grouped over the shared KV heads without
    repeating K/V and the probabilities meet V in the served dtype, as
    in :func:`_attend_cached`: the same numbers, up to the order of the
    softmax's sums."""
    b, h, cq, d = q.shape
    group = h // kv_heads
    scale = d ** -0.5 if scale is None else scale
    qg = q.reshape(b, kv_heads, group, cq, d)

    def scores_of(k, _):
        return jnp.einsum(
            "bhgqd,bhkd->bhgqk", qg, k).astype(jnp.float32) * scale

    def context_of(weights, _, v):
        return jnp.einsum("bhgqk,bhkd->bhgqd", weights.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    out = attend_key_blocks(view_block, block_rows, scores_of, context_of,
                            q_positions, (kv_heads, group), d, window)
    return out.reshape(b, h, cq, d)


def _latent_chunk_layers(params, config: TransformerConfig, cache: Dict,
                         x, positions):
    """A latent block's layers of a width-C cached step: each
    sub-layer writes its chunk's latent rows at ``positions`` and
    attends the whole dense cache in the absorbed form — the paged
    steps' math (serving/paged.py) over a lockstep cache."""
    batch, chunk = x.shape[:2]
    start = positions[0]
    positions = jnp.broadcast_to(positions[None, :], (batch, chunk))
    cache_k, cache_v = cache["k"], cache["v"]

    def attend(sub, attn, y):
        nonlocal cache_k, cache_v
        q_nope, q_rope, c_kv, k_rope = latent_qkv(
            attn, y, positions, config)
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, c_kv[None, :, None], (sub, 0, 0, start, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, k_rope[None, :, None], (sub, 0, 0, start, 0))
        return latent_attend(attn, q_nope, q_rope, cache_k[sub, :, 0],
                             cache_v[sub, :, 0], positions, config,
                             absorbed=True)

    x, _ = latent_layers(params, x, config, attend)
    return x, {"k": cache_k, "v": cache_v,
               "length": cache["length"] + chunk}


def _chunk_head(params, config: TransformerConfig, x, head_last_only,
                head_row):
    """Final norm and ``lm_head`` over every row of a chunk, its last
    row, or the one row ``head_row``."""
    x = _rms_norm(x, params["final_norm"]["scale"], config.norm_eps)
    if head_last_only:
        x = x[:, -1:]
    elif head_row is not None:
        x = x[:, head_row: head_row + 1]
    return (x @ params["lm_head"].astype(config.dtype)).astype(jnp.float32)


def _decode_chunk(params, config: TransformerConfig, cache: Dict,
                  tokens: jax.Array, head_last_only: bool = False,
                  head_row: Optional[int] = None):
    """A width-C cached step: tokens [batch, C] at positions
    ``length .. length+C-1`` -> (logits [batch, C, vocab], cache).

    C = 1 is the decode step; C > 1 is a prefill chunk — the chunk's
    K/V land in the cache first, then its queries attend the whole
    cache under the per-query causal band, so intra-chunk causality
    falls out of the same mask that orders chunk vs history.

    ``head_last_only``: project lm_head over the final position only
    (logits [batch, 1, vocab]) — prefill needs just the last row, and a
    full [batch, C, vocab] f32 buffer would otherwise dominate the
    chunked step's activations at real vocab sizes.  ``head_row``
    selects a single OTHER row instead (the pad-forward ragged prefill,
    whose last real token is not the chunk's last row)."""
    dtype = config.dtype
    position = cache["length"]
    chunk = tokens.shape[1]
    positions = position + jnp.arange(chunk)  # global positions [C]
    x = params["embed"][tokens].astype(dtype)  # [b,C,d]
    if config.latent:
        x, cache = _latent_chunk_layers(params, config, cache, x, positions)
        return _chunk_head(params, config, x, head_last_only, head_row), cache
    use_rope = config.positional == "rope"
    if not use_rope:
        pos_embed = jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], position, chunk)
        x = x + pos_embed.astype(dtype)

    new_k, new_v = [], []
    for layer_idx, layer in enumerate(params["layers"]):
        y = _rms_norm(x, layer["norm1"]["scale"])
        q = jnp.einsum("bsd,dhk->bhsk", y, layer["attn"]["wq"].astype(dtype))
        k = jnp.einsum("bsd,dhk->bhsk", y, layer["attn"]["wk"].astype(dtype))
        v = jnp.einsum("bsd,dhk->bhsk", y, layer["attn"]["wv"].astype(dtype))
        if use_rope:
            q = apply_rope(q, positions)
            k = apply_rope(k, positions)
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"][layer_idx], k, position, axis=2
        )
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"][layer_idx], v, position, axis=2
        )
        new_k.append(cache_k)
        new_v.append(cache_v)
        o = _attend_cached(
            q, cache_k, cache_v, positions, window=config.attention_window
        ).astype(dtype)
        x = x + jnp.einsum("bhsk,hkd->bsd", o, layer["attn"]["wo"].astype(dtype))
        y = _rms_norm(x, layer["norm2"]["scale"])
        if "moe" in layer:
            # per-chunk MoE: routing is per-token (top-k).  A factor-
            # derived capacity over batch*chunk tokens could drop rows
            # that share an expert; capacity = the chunk's token count
            # guarantees no drops (a token routes to an expert at most
            # once), keeping routing position- and batch-independent.
            from ..ops.moe import MoEConfig, moe_apply

            _check_moe_decodable(config)
            e, d_m, f = layer["moe"]["w_in"].shape
            out, _ = moe_apply(
                layer["moe"], y,
                MoEConfig(d_model=d_m, d_ff=f, num_experts=e,
                          capacity_factor=config.moe_capacity_factor,
                          top_k=config.moe_top_k,
                          dispatch=config.moe_dispatch),
                capacity=y.shape[0] * y.shape[1],
            )
            x = x + out.astype(dtype)
        else:
            y = jax.nn.gelu(y @ layer["mlp"]["w_in"].astype(dtype))
            x = x + y @ layer["mlp"]["w_out"].astype(dtype)

    logits = _chunk_head(params, config, x, head_last_only, head_row)
    cache = {
        "k": jnp.stack(new_k),
        "v": jnp.stack(new_v),
        "length": position + chunk,
    }
    return logits, cache


def _decode_one(params, config: TransformerConfig, cache: Dict, token: jax.Array):
    """One decode step: token [batch] -> (logits [batch, vocab], cache)."""
    logits, cache = _decode_chunk(params, config, cache, token[:, None])
    return logits[:, 0], cache


def prefill(params, config: TransformerConfig, prompt: jax.Array) -> Tuple[Dict, jax.Array]:
    """Feed the prompt [batch, prompt_len] through the cache; returns
    (cache, last_logits).

    Runs as ONE dense forward pass (flash kernel and all) that also
    collects every layer's roped K/V projections and writes them into
    the cache in bulk — not a token-at-a-time scan, whose [b, 1, d]
    matmuls leave the MXU idle and serialize prompt_len dispatches.
    The incremental variant survives as :func:`prefill_incremental`
    (the equivalence oracle, and the path for ring/ulysses configs
    whose dense entry is sequence-sharded)."""
    from .transformer import _forward, _select_attention

    batch, prompt_len = prompt.shape
    _check_prompt_fits(config, prompt_len)
    # same refusal as the decode step: the cache this prefill feeds could
    # never be decoded from anyway
    _check_moe_decodable(config)
    if config.attention in ("ring", "ulysses"):
        return prefill_incremental(params, config, prompt)
    if config.latent:
        # no bulk forward collects latent rows: one cached chunk does
        logits, cache = _decode_chunk(
            params, config, init_kv_cache(config, batch), prompt,
            head_last_only=True)
        return cache, logits[:, 0]
    kv_sink: list = []
    hidden, _ = _forward(params, prompt, config, _select_attention(config),
                         0, apply_head=False, kv_sink=kv_sink)
    cache = init_kv_cache(config, batch)
    k_all = jnp.stack([k for k, _ in kv_sink]).astype(config.dtype)
    v_all = jnp.stack([v for _, v in kv_sink]).astype(config.dtype)
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], k_all, (0, 0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], v_all, (0, 0, 0, 0, 0))
    cache["length"] = jnp.asarray(prompt_len, jnp.int32)
    last_logits = (
        hidden[:, -1] @ params["lm_head"].astype(config.dtype)
    ).astype(jnp.float32)
    return cache, last_logits


def bucket_width(remainder: int, chunk: int) -> int:
    """The power-of-two chunk width covering ``remainder`` tokens
    (capped at ``chunk``).  Bucketing the ragged final chunk bounds a
    serving host's compiled prefill shapes at O(log chunk) instead of
    one per distinct remainder — the serving engine's prefill planner
    uses the same buckets (serving/engine.py)."""
    if not 0 < remainder <= chunk:
        raise ValueError(f"remainder {remainder} not in 1..{chunk}")
    width = 1
    while width < remainder:
        width *= 2
    return min(width, chunk)


def prefill_chunked(
    params, config: TransformerConfig, prompt: jax.Array, chunk: int,
) -> Tuple[Dict, jax.Array]:
    """Prefill in fixed-size chunks: each chunk is one cached step
    (:func:`_decode_chunk`), so peak activation memory is O(chunk)
    instead of the bulk path's O(prompt_len) — the long-prompt regime —
    while every chunk still runs MXU-shaped [b, chunk, d] matmuls
    rather than the incremental path's [b, 1, d] slivers.

    Ragged prompts are allowed: the tail past the last full chunk runs
    as ONE extra chunk of the next power-of-two width (``bucket_width``),
    sliding its start BACK over already-written positions — recomputing
    identical K/V, so the overwrite is a no-op — so that its last row is
    the prompt's last real token.  A prompt shorter than its own bucket
    pads forward instead; its dead rows are zeroed and the returned
    logits taken at the last real row, keeping the cache and logits
    bit-equal to the bulk prefill's.  Distinct remainders therefore cost
    at most O(log chunk) compiled chunk shapes, not one each."""
    batch, prompt_len = prompt.shape
    _check_prompt_fits(config, prompt_len)
    _check_moe_decodable(config)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    cache = init_kv_cache(config, batch)
    n_full, remainder = divmod(prompt_len, chunk)
    last_logits = None

    if n_full:
        def step(cache, chunk_tokens):
            logits, cache = _decode_chunk(params, config, cache,
                                          chunk_tokens.T, head_last_only=True)
            return cache, logits[:, 0]

        chunks = prompt[:, : n_full * chunk].T.reshape(n_full, chunk, batch)
        cache, scan_logits = jax.lax.scan(step, cache, chunks)
        last_logits = scan_logits[-1]
    if remainder == 0:
        return cache, last_logits

    # cap at the cache bound: a short model (max_seq_len below the
    # bucket) must not pad past its own cache (the cap can only bind in
    # the pad-forward branch, where prompt_len <= max_seq_len < width)
    width = min(bucket_width(remainder, chunk), config.max_seq_len)
    if prompt_len >= width:
        # slide the final chunk back so it ENDS at the last real token
        tail = prompt[:, prompt_len - width:]
        cache = dict(cache, length=jnp.asarray(prompt_len - width, jnp.int32))
        tail_logits, cache = _decode_chunk(params, config, cache, tail,
                                           head_last_only=True)
        return cache, tail_logits[:, 0]

    # n_full == 0 and the bucket overshoots the prompt: pad the tail.
    # The pad rows' outputs are discarded and their K/V zeroed below, so
    # the returned cache matches the bulk prefill's exactly (decode from
    # it is bit-identical).
    padded = jnp.pad(prompt, ((0, 0), (0, width - prompt_len)))
    row_logits, cache = _decode_chunk(params, config, cache, padded,
                                      head_row=prompt_len - 1)
    cache["k"] = cache["k"].at[:, :, :, prompt_len:width, :].set(0)
    cache["v"] = cache["v"].at[:, :, :, prompt_len:width, :].set(0)
    cache = dict(cache, length=jnp.asarray(prompt_len, jnp.int32))
    return cache, row_logits[:, 0]


def prefill_incremental(
    params, config: TransformerConfig, prompt: jax.Array
) -> Tuple[Dict, jax.Array]:
    """Token-at-a-time prefill via the decode step: the equivalence
    oracle for the bulk prefill, and the fallback for configs whose
    dense forward cannot run here.  Exactly the chunked path at width 1
    — one scan body to maintain."""
    return prefill_chunked(params, config, prompt, 1)


def greedy_decode_with_cache(
    params,
    config: TransformerConfig,
    cache: Dict,
    last_logits: jax.Array,
    max_new_tokens: int,
    prefill_length: Optional[int] = None,
) -> jax.Array:
    """Greedy continuation from a prefilled cache — the serving split:
    prefill once (bulk or chunked), decode from its (cache, logits).
    Returns [batch, max_new_tokens] token ids; jit-compatible.

    When this call is jitted (cache length traced), pass the static
    ``prefill_length`` so the capacity bound is enforced at trace time —
    without it, a continuation from a nearly-full cache cannot be
    caught and would clamp-overwrite the last cache slot."""
    _check_cache_headroom(cache, max_new_tokens, prefill_length)
    first_token = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)

    def step(carry, _):
        cache, token = carry
        next_logits, cache = _decode_one(params, config, cache, token)
        next_token = jnp.argmax(next_logits, axis=-1).astype(jnp.int32)
        return (cache, next_token), next_token

    # first token comes straight from the prefill logits; scan emits the
    # remaining max_new_tokens-1 (no wasted trailing forward pass)
    (_, _), rest = jax.lax.scan(
        step, (cache, first_token), None, length=max_new_tokens - 1
    )
    tokens = jnp.concatenate([first_token[None], rest], axis=0)
    return tokens.T  # [batch, new_tokens]


def greedy_decode(
    params, config: TransformerConfig, prompt: jax.Array, max_new_tokens: int
) -> jax.Array:
    """Greedy generation: returns [batch, max_new_tokens] token ids.
    Jit-compatible (static max_new_tokens)."""
    total = prompt.shape[1] + max_new_tokens
    if total > config.max_seq_len:
        # dynamic_update_slice would silently clamp at the window edge and
        # overwrite the last cache slot; fail loudly instead
        raise ValueError(
            f"prompt ({prompt.shape[1]}) + max_new_tokens ({max_new_tokens}) "
            f"= {total} exceeds max_seq_len {config.max_seq_len}"
        )
    cache, logits = prefill(params, config, prompt)
    return greedy_decode_with_cache(params, config, cache, logits,
                                    max_new_tokens)


def _check_speculative_args(
    config: TransformerConfig,
    draft_config: TransformerConfig,
    prompt_len: int,
    max_new_tokens: int,
    draft_len: int,
) -> None:
    """Shared validation for both speculative decoders: generation
    length, draft width, vocabulary match, and draft_len slots of cache
    headroom in BOTH models."""
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if draft_len < 2:
        raise ValueError(f"draft_len must be >= 2, got {draft_len}")
    if config.vocab_size != draft_config.vocab_size:
        raise ValueError(
            f"target and draft vocabularies differ "
            f"({config.vocab_size} vs {draft_config.vocab_size})"
        )
    total = prompt_len + max_new_tokens + draft_len
    for name, c in (("target", config), ("draft", draft_config)):
        if total > c.max_seq_len:
            raise ValueError(
                f"prompt + max_new_tokens + draft_len = {total} exceeds "
                f"the {name} max_seq_len {c.max_seq_len} (speculation "
                f"needs draft_len slots of cache headroom)"
            )


def speculative_acceptance(proposal: jax.Array, targets: jax.Array) -> jax.Array:
    """The exact-match acceptance rule every speculative decoder here
    shares: count the leading proposed tokens the target's own picks
    agree with.  ``proposal`` [..., k] holds the drafted tokens,
    ``targets`` [..., >= k] the tokens the target model itself emits at
    those positions (greedy argmax, or the categorical draw under that
    position's PRNG key); the return value is int32 [...] in ``0..k`` —
    the longest prefix of the draft that sequential decoding would have
    produced anyway.  The emitted round is then ``targets[..., :m + 1]``
    (the matched prefix IS the target's picks, plus the correction /
    bonus pick from the same verify pass), which is what makes
    speculative streams bit-exact with speculation off by construction.

    Used by the dense draft-model decoder below (batch rows share one
    cache length, so it accepts ``min`` over rows) and by the paged
    serving verifier (``serving/paged.paged_verify_span``, per-lane
    counts).  Unused proposal slots must carry an impossible token
    (e.g. -1) so a pad can never count as a match.
    """
    matches = jnp.cumprod(
        (proposal == targets[..., : proposal.shape[-1]]).astype(jnp.int32),
        axis=-1)
    return jnp.sum(matches, axis=-1)


def speculative_greedy_decode(
    params,
    config: TransformerConfig,
    draft_params,
    draft_config: TransformerConfig,
    prompt: jax.Array,
    max_new_tokens: int,
    draft_len: int = 4,
    return_stats: bool = False,
) -> jax.Array:
    """Greedy generation with draft-model speculation: matches
    :func:`greedy_decode`'s token stream up to floating-point argmax
    ties, in fewer target-model passes.  (The width-``draft_len`` verify
    chunk reduces its matmuls in a different order than width-1 steps, so
    a near-tied argmax can diverge on real hardware — bf16 especially;
    the equivalence tests lock exactness on CPU f32 small models.)

    Each round the draft proposes ``draft_len - 1`` tokens one at a time
    (cheap model, tiny steps), then the target verifies the whole
    proposal in ONE width-``draft_len`` cached chunk (
    :func:`_decode_chunk` — an MXU-shaped matmul instead of draft_len
    tiny steps).  The longest matching prefix is accepted plus the
    target's own next token (the standard greedy acceptance rule, which
    preserves the target's exact argmax stream); a mismatch costs
    nothing — the correction token comes from the same verify pass.
    Batched rows share the cache length, so acceptance is the minimum
    across rows (batch 1 gets the full per-round speedup).

    The verify chunk writes its K/V optimistically; rejected positions
    are simply masked out by the rewound cache length and overwritten by
    the next round.  Both models must share a vocabulary; the caches
    need headroom of ``draft_len`` beyond the generated text.  With
    ``return_stats`` the result is ``(tokens, {"rounds": r})`` — r counts
    target verify passes (the speculation speedup's denominator; on real
    hardware near-tied argmaxes can reject even a self-draft, so measured
    ceilings should report it)."""
    batch, prompt_len = prompt.shape
    _check_speculative_args(config, draft_config, prompt_len,
                            max_new_tokens, draft_len)

    cache, logits = prefill(params, config, prompt)
    dcache, _ = prefill(draft_params, draft_config, prompt)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [b]
    out = jnp.zeros((batch, max_new_tokens + draft_len), jnp.int32)
    out = out.at[:, 0].set(first)

    def cond(state):
        return state[3] < max_new_tokens

    def body(state):
        cache, dcache, out, n_done, last, rounds = state

        # 1. draft proposes draft_len-1 tokens after `last`.  The scan
        # runs draft_len steps: the final step feeds p_{k-1} (its output
        # is discarded) so the draft cache holds K/V for every token the
        # round may accept — a full accept needs p_{k-1}'s entry.
        def draft_step(carry, _):
            dc, tok = carry
            lg, dc = _decode_one(draft_params, draft_config, dc, tok)
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            return (dc, nxt), nxt

        (dcache, _), proposal = jax.lax.scan(
            draft_step, (dcache, last), None, length=draft_len)
        proposal = proposal.T[:, :draft_len - 1]  # [b, draft_len-1]

        # 2. target verifies the whole round in one chunk: inputs
        # [last, p_1..p_{k-1}] -> greedy targets t_1..t_k (t_k = bonus)
        chunk = jnp.concatenate([last[:, None], proposal], axis=1)
        target_length = cache["length"]
        chunk_logits, cache = _decode_chunk(params, config, cache, chunk)
        targets = jnp.argmax(chunk_logits, axis=-1).astype(jnp.int32)

        # 3. longest matching prefix, shared across rows (one cache length)
        m = jnp.min(speculative_acceptance(proposal, targets))  # 0..k-1

        # 4. the emitted stream: p_1..p_m then the target's correction /
        # bonus t_{m+1}; positions past m are speculative garbage that
        # later rounds overwrite (and the final slice drops)
        idx = jnp.arange(draft_len)
        stream = jnp.where(
            idx[None, :] < m,
            jnp.pad(proposal, ((0, 0), (0, 1))),
            targets,
        )
        out = jax.lax.dynamic_update_slice(out, stream, (0, n_done))

        # 5. keep only the consumed inputs' K/V: [last, p_1..p_m] —
        # rejected (and draft-overshoot) entries are masked by the
        # rewound length and overwritten next round
        cache = dict(cache, length=target_length + m + 1)
        dcache = dict(dcache, length=target_length + m + 1)
        last = stream[:, m]
        return cache, dcache, out, n_done + m + 1, last, rounds + 1

    _, _, out, _, _, rounds = jax.lax.while_loop(
        cond, body, (cache, dcache, out, jnp.int32(1), first, jnp.int32(0)))
    tokens = out[:, :max_new_tokens]
    return (tokens, {"rounds": rounds}) if return_stats else tokens


def speculative_sample_decode(
    params,
    config: TransformerConfig,
    draft_params,
    draft_config: TransformerConfig,
    prompt: jax.Array,
    rng: jax.Array,
    max_new_tokens: int,
    draft_len: int = 4,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    return_stats: bool = False,
) -> jax.Array:
    """Sampled generation with draft-model speculation: the emitted
    stream has EXACTLY the target model's sampling distribution (the
    standard speculative-sampling rejection rule — accept draft token x
    with probability min(1, p(x)/q(x)); on rejection, resample from the
    residual norm(max(p - q, 0)); a fully-accepted round earns a bonus
    token from the target's next-position distribution).  ``p`` and
    ``q`` are the temperature/top-k/top-p-FILTERED distributions of the
    target and draft, so the output matches :func:`sample_decode` with
    the same filters (VERDICT r4 #5).

    Round structure (cache rewind, batch-min acceptance, optimistic K/V)
    is shared with :func:`speculative_greedy_decode`; rows that accepted
    beyond the batch-min simply re-draft those tokens next round, which
    leaves the emitted distribution untouched (unemitted acceptances are
    discarded, never revealed).  ``temperature=0`` delegates to the
    greedy variant.  With ``return_stats`` the result is
    ``(tokens, {"rounds": r})`` — r counts target verify passes, the
    speculation speedup's denominator."""
    batch, prompt_len = prompt.shape
    _check_speculative_args(config, draft_config, prompt_len,
                            max_new_tokens, draft_len)
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    _filter_logits(jnp.zeros((1, 2)), top_k, top_p)
    if temperature == 0.0:
        return speculative_greedy_decode(
            params, config, draft_params, draft_config, prompt,
            max_new_tokens, draft_len, return_stats=return_stats)

    def log_dist(logits):
        # filtered + temperature-scaled log-distribution over the last
        # axis; _filter_logits is [rows, vocab]-shaped, so fold any
        # leading dims (the verify chunk is [b, k, vocab])
        flat = logits.reshape(-1, logits.shape[-1])
        out = jax.nn.log_softmax(
            _filter_logits(flat / temperature, top_k, top_p), axis=-1)
        return out.reshape(logits.shape)

    cache, logits = prefill(params, config, prompt)
    dcache, _ = prefill(draft_params, draft_config, prompt)
    rng, first_key = jax.random.split(rng)
    first = jax.random.categorical(
        first_key, log_dist(logits), axis=-1).astype(jnp.int32)
    out = jnp.zeros((batch, max_new_tokens + draft_len), jnp.int32)
    out = out.at[:, 0].set(first)

    def cond(state):
        return state[3] < max_new_tokens

    def body(state):
        cache, dcache, out, n_done, last, rng, rounds = state
        rng, draft_rng, accept_key, fix_key = jax.random.split(rng, 4)

        # 1. draft proposes draft_len-1 SAMPLED tokens after `last`,
        # keeping each position's full filtered log-distribution q (the
        # acceptance test and the residual both need it).  The final
        # step feeds p_{k-1} so the draft cache covers a full accept.
        def draft_step(carry, key):
            dc, tok = carry
            lg, dc = _decode_one(draft_params, draft_config, dc, tok)
            logq = log_dist(lg)
            nxt = jax.random.categorical(key, logq, axis=-1).astype(jnp.int32)
            return (dc, nxt), (nxt, logq)

        (dcache, _), (proposal_all, logq_all) = jax.lax.scan(
            draft_step, (dcache, last),
            jax.random.split(draft_rng, draft_len))
        proposal = proposal_all.T[:, :draft_len - 1]   # [b, k-1]
        logq = logq_all[:draft_len - 1]                # [k-1, b, vocab]

        # 2. target verifies the round in one chunk: filtered log-p at
        # every position ([b, k, vocab] -> [k, b, vocab] to align with q)
        chunk = jnp.concatenate([last[:, None], proposal], axis=1)
        target_length = cache["length"]
        chunk_logits, cache = _decode_chunk(params, config, cache, chunk)
        logp = jnp.moveaxis(log_dist(chunk_logits), 1, 0)  # [k, b, vocab]

        # 3. rejection rule per proposal position: accept x_i w.p.
        # min(1, p(x_i)/q(x_i)); leading-accept count, batch-min shared
        # (one cache length for all rows)
        def gather(dist, tok):  # [k-1, b, vocab], [b, k-1] -> [k-1, b]
            return jnp.take_along_axis(
                dist, tok.T[..., None], axis=-1)[..., 0]

        ratio = gather(logp[:draft_len - 1], proposal) - gather(logq, proposal)
        u = jax.random.uniform(accept_key, ratio.shape)
        accepted = jnp.log(u) < jnp.minimum(ratio, 0.0)     # [k-1, b]
        matches = jnp.cumprod(accepted.T.astype(jnp.int32), axis=1)
        m = jnp.min(jnp.sum(matches, axis=1))  # 0..draft_len-1

        # 4. the token at emitted position m+1, per row:
        #    - its row rejected x_{m+1} (accept count == m < k-1):
        #      residual sample from norm(max(p_{m+1} - q_{m+1}, 0))
        #    - its row accepted past m (count > m): x_{m+1} itself
        #    - m == k-1 (every row accepted everything): bonus from
        #      p_k — logp[draft_len-1], where no q exists
        # rows are independent here; only the SHARED length forced m.
        bonus = m == draft_len - 1
        pos = jnp.minimum(m, draft_len - 2)
        p_m = jnp.take(logp, jnp.where(bonus, draft_len - 1, pos), axis=0)
        q_m = jnp.take(logq, pos, axis=0)
        residual = jnp.clip(jnp.exp(p_m) - jnp.exp(q_m), 0.0, None)
        # numerically-empty residual (p == q exactly): any mass works —
        # acceptance almost surely fired first; fall back to p
        empty = jnp.sum(residual, axis=-1, keepdims=True) <= 1e-9
        fix_dist = jnp.where(
            bonus, p_m,
            jnp.where(empty, p_m, jnp.log(
                jnp.where(residual > 0, residual, 1e-38))))
        fix = jax.random.categorical(
            fix_key, fix_dist, axis=-1).astype(jnp.int32)
        row_accepts = jnp.sum(matches, axis=1)  # [b]
        next_prop = jnp.where(
            bonus, fix,
            jnp.where(row_accepts > m,
                      jnp.take_along_axis(
                          proposal, pos[None, None].repeat(batch, 0),
                          axis=1)[:, 0],
                      fix))

        # 5. emitted stream: x_1..x_m then next_prop; positions past m
        # are speculative garbage later rounds overwrite
        idx = jnp.arange(draft_len)
        stream = jnp.where(
            idx[None, :] < m,
            jnp.pad(proposal, ((0, 0), (0, 1))),
            jnp.where(idx[None, :] == m, next_prop[:, None], 0),
        )
        out = jax.lax.dynamic_update_slice(out, stream, (0, n_done))

        cache = dict(cache, length=target_length + m + 1)
        dcache = dict(dcache, length=target_length + m + 1)
        last = stream[:, m]
        return cache, dcache, out, n_done + m + 1, last, rng, rounds + 1

    _, _, out, _, _, _, rounds = jax.lax.while_loop(
        cond, body,
        (cache, dcache, out, jnp.int32(1), first, rng, jnp.int32(0)))
    tokens = out[:, :max_new_tokens]
    return (tokens, {"rounds": rounds}) if return_stats else tokens


def _filter_logits(
    logits: jax.Array,
    top_k: Optional[int],
    top_p: Optional[float],
) -> jax.Array:
    """Restrict [batch, vocab] logits to the top-k / nucleus (top-p) set,
    -inf elsewhere.  Static-shape throughout (full sort, no dynamic
    narrowing) — the jit/TPU-compatible formulation."""
    if top_k is not None:
        if top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        # clamp so top_k >= vocab intentionally keeps everything (rather
        # than leaning on JAX's silent out-of-bounds index clamping)
        k = min(top_k, logits.shape[-1])
        kth = jnp.sort(logits, axis=-1)[:, -k][:, None]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    if top_p is not None:
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]  # descending
        cum = jnp.cumsum(jax.nn.softmax(sorted_logits, axis=-1), axis=-1)
        # keep the smallest prefix whose mass reaches top_p: a token stays
        # if the cumulative mass BEFORE it is still < top_p
        keep_sorted = jnp.concatenate(
            [jnp.ones_like(cum[:, :1], bool), cum[:, :-1] < top_p], axis=-1
        )
        # threshold back in vocab order: lowest kept logit per row
        cutoff = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1
        )[:, None]
        logits = jnp.where(logits >= cutoff, logits, -jnp.inf)
    return logits


def sample_decode(
    params,
    config: TransformerConfig,
    prompt: jax.Array,
    rng: jax.Array,
    max_new_tokens: int,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> jax.Array:
    """Sampled generation: temperature / top-k / nucleus (top-p), any
    combination (k-restriction first, then nucleus — the conventional
    order).  ``temperature=0`` is exact greedy.  Returns
    [batch, max_new_tokens] token ids; jit-compatible like greedy_decode
    (one compiled scan, static shapes, PRNG split per step).  With a
    draft model available, :func:`speculative_sample_decode` emits the
    SAME distribution in fewer target passes."""
    total = prompt.shape[1] + max_new_tokens
    if total > config.max_seq_len:
        raise ValueError(
            f"prompt ({prompt.shape[1]}) + max_new_tokens ({max_new_tokens}) "
            f"= {total} exceeds max_seq_len {config.max_seq_len}"
        )
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    # validate the filter arguments BEFORE the prefill forward, so a bad
    # top_k/top_p fails fast on every temperature
    _filter_logits(jnp.zeros((1, 2)), top_k, top_p)
    if temperature == 0.0:
        return greedy_decode(params, config, prompt, max_new_tokens)
    cache, logits = prefill(params, config, prompt)
    return sample_decode_with_cache(
        params, config, cache, logits, rng, max_new_tokens,
        temperature=temperature, top_k=top_k, top_p=top_p)


def sample_decode_with_cache(
    params,
    config: TransformerConfig,
    cache: Dict,
    last_logits: jax.Array,
    rng: jax.Array,
    max_new_tokens: int,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    prefill_length: Optional[int] = None,
) -> jax.Array:
    """Sampled continuation from a prefilled cache (the serving split,
    like :func:`greedy_decode_with_cache`).  Jitted callers should pass
    the static ``prefill_length`` — see greedy_decode_with_cache."""
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    _filter_logits(jnp.zeros((1, 2)), top_k, top_p)
    if temperature == 0.0:
        return greedy_decode_with_cache(params, config, cache, last_logits,
                                        max_new_tokens, prefill_length)
    _check_cache_headroom(cache, max_new_tokens, prefill_length)

    def pick(logits, key):
        # conventional order: temperature first, then the k/nucleus
        # restriction on the scaled distribution (top_k is scale-invariant
        # but top_p is not)
        filtered = _filter_logits(logits / temperature, top_k, top_p)
        return jax.random.categorical(key, filtered, axis=-1).astype(jnp.int32)

    rng, first_key = jax.random.split(rng)
    first_token = pick(last_logits, first_key)

    def step(carry, key):
        cache, token = carry
        next_logits, cache = _decode_one(params, config, cache, token)
        next_token = pick(next_logits, key)
        return (cache, next_token), next_token

    step_keys = jax.random.split(rng, max_new_tokens - 1)
    (_, _), rest = jax.lax.scan(step, (cache, first_token), step_keys)
    tokens = jnp.concatenate([first_token[None], rest], axis=0)
    return tokens.T  # [batch, new_tokens]
