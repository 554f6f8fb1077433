"""Flagship model: decoder-only Transformer LM, designed mesh-first.

This is the model ``__graft_entry__`` exposes and the multi-chip dry run
shards.  Every weight has a named-sharding rule over the (dp, tp, sp) mesh
(``transformer_sharding_rules``): attention heads and MLP hidden split over
tp, embeddings split over tp's feature axis, activations batch-split over dp
and sequence-split over sp (ring attention).  bf16 activations by default —
MXU-friendly — with f32 parameters/optimizer.

The reference framework contains no model code (SURVEY §2.10); this is the
distributed-workload half the prompt makes first-class.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import attention_reference, flash_attention
from ..ops.ring_attention import ring_attention, ring_flash_attention
from ..ops.rope import apply_rope, rope_positions


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_seq_len: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    attention: str = "auto"  # auto | reference | flash | ring
    attention_window: Optional[int] = None  # sliding-window (local) size
    # grouped-query attention: KV heads shared by query-head groups
    # (None = n_heads, plain MHA; 1 = MQA).  Shrinks the decode KV cache
    # and its HBM traffic by n_heads/n_kv_heads — the ops (flash, ring,
    # ulysses, the hand-scheduled backwards) are already GQA-aware.
    n_kv_heads: Optional[int] = None
    positional: str = "learned"  # learned | rope
    remat: bool = False  # jax.checkpoint each layer (HBM for FLOPs)
    # MoE: every Nth layer's MLP becomes a top-k-routed expert mixture
    # (ops.moe dense dispatch); None = all-dense
    moe_every: Optional[int] = None
    moe_num_experts: int = 8
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 1
    # "tokens_choose" (top-k) or "experts_choose" (balanced-by-
    # construction; training-time only — incremental decode refuses it)
    moe_routing: str = "tokens_choose"
    # "scatter" (permutation dispatch, no dispatch FLOPs) or "einsum"
    # (dense one-hot dispatch); see ops.moe
    moe_dispatch: str = "scatter"
    # rotary base and RMSNorm epsilon (the dense block's constants)
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    # block kind.  "dense": norm -> MHA/MQA/GQA -> norm -> two-matrix
    # GELU MLP (or a moe_every mixture).  The two latent kinds share
    # the latent (MLA) attention, the gated (SwiGLU) FFN and the routed
    # expert layer, and differ in how a layer is put together.
    # "latent_shortcut": a DOUBLE layer — two latent attentions, two
    # dense gated FFNs of width d_ff, and ONE routed expert layer that
    # reads the first sub-layer's post-attention norm and is added after
    # the second sub-layer's FFN (the shortcut).  "latent_moe": a SINGLE
    # layer — one latent attention and one feed-forward, which is a
    # dense gated FFN of width d_ff in the first first_dense_layers
    # layers and the routed expert layer beside n_shared_experts
    # always-on experts in the rest.  The fields below describe them
    # and mean nothing to the dense block.
    block: str = "dense"
    q_lora_rank: int = 0  # query down-projection rank
    kv_lora_rank: int = 0  # cached latent width
    qk_nope_head_dim: int = 0  # per-head no-position q.k width
    qk_rope_head_dim: int = 0  # rotary q.k width (one key for all heads)
    v_head_dim: int = 0
    # whether q and the cached latent are multiplied by
    # sqrt(d_model / their rank) after their projections
    mla_rank_scaling: bool = True
    # the expert layer: a router over n_routed_experts + n_zero_experts
    # outputs (zero-compute experts return their input), router_top_k
    # choices a token, no capacity, nothing dropped.  The router's law
    # (ops/moe.py router_choices): scores are the softmax over all
    # outputs or each output's sigmoid; with router_choice_bias a
    # per-expert bias is added to the scores for the CHOICE only; the
    # chosen scores are renormalised to sum to one or left as they are,
    # then multiplied by routed_scaling_factor.  This device holds
    # experts_held of the routed experts from first_expert_held on
    # (None = all) and leaves out what the others would add (ops/moe.py
    # routed_experts_apply)
    n_routed_experts: int = 0
    n_zero_experts: int = 0
    router_top_k: int = 0
    routed_scaling_factor: float = 1.0
    router_scoring: str = "softmax"  # softmax | sigmoid
    router_choice_bias: bool = False
    router_renormalise: bool = False
    expert_d_ff: int = 0
    experts_held: Optional[int] = None
    first_expert_held: int = 0
    # "latent_moe" only: always-on experts of width expert_d_ff each,
    # and the leading layers whose feed-forward is dense
    n_shared_experts: int = 0
    first_dense_layers: int = 0
    # "gqa_moe": the dense block's attention and cache row (a K and a V
    # a KV head) at an explicit head width (None = d_model // n_heads;
    # n_heads x head_width need not be d_model), with an RMSNorm over
    # each head's q and k before the rotation, and the routed expert
    # layer above as EVERY layer's feed-forward (no dense MLP, no shared
    # expert).  rope_theta and norm_eps are its own.
    head_width: Optional[int] = None
    # the generation law, "gqa_moe" only.  diffusion_block B = 0: one
    # token after another under the causal mask.  B > 0: generation by
    # diffusion over aligned blocks of B positions — row i sees row j
    # iff j // B <= i // B (bidirectional inside a block, causal across
    # blocks); a block's unknown rows hold mask_token's embedding and
    # are committed over diffusion_steps denoising passes, the most
    # confident rows first (serving/paged.py paged_diffusion_pass)
    diffusion_block: int = 0
    diffusion_steps: int = 0
    mask_token: int = 0
    # "retention": power-retention layers (ops/retention.py) — 'gqa_moe''s
    # projections (explicit head width, per-head q/k norms, rotate-half
    # rope at its own rope_theta) and a log gate a KV head a row; the
    # weight of key j for query i is exp(A_i - A_j) (q_i . k_j)^2 / hd
    # over the sum of the weights, no softmax; the feed-forward is a dense
    # gated FFN of width d_ff in every layer.  Served from a recurrent
    # state a lane beside a short paged tail of keys and values
    # (serving/paged.py _retention_layers); it has no field of its own
    # layers that name their OPERATOR ("gqa_moe" under the causal mask
    # only): None, every layer's operator is the block's attention; else
    # one name a layer, "attention" (the block's, with a cache row in the
    # paged pool) or "conv" (the gated short convolution of
    # ops/short_conv.py over conv_taps rows: no cache row, a state of
    # conv_taps - 1 rows of d_model values a lane, BY SLOT beside the
    # pool).  The feed-forward behind either is the block's: a dense gated
    # FFN of width d_ff in the first first_dense_layers layers, the routed
    # experts in the rest
    layer_operators: Optional[Tuple[str, ...]] = None
    conv_taps: int = 0
    # what the renormalised weights' sum is kept off zero by
    router_renormalise_eps: float = 1e-20
    # two more attention KINDS a layer may name beside "attention" (the
    # block's: every earlier row, rotated): "global" sees every earlier row
    # and rotates NOTHING (no position enters it), "window" sees row j from
    # row i iff 0 <= i - j < attention_window and rotates.  A model that
    # names "window" caches BY KIND: the "attention"/"global" layers' rows
    # in one pool, the "window" layers' in another whose pages behind the
    # window go back while the request runs (serving/kv_blocks.py).  Three
    # switches of the 'gqa_moe' layer, each as every earlier configuration
    # has it by default: the RMSNorm over each head's q and k; what the
    # router reads, "post_attention" (norm2's output, as the experts) or
    # "layer_input" (the residual stream entering the layer, before norm1
    # and the attention); and the experts' gate activation, "silu" | "relu"
    qk_norm: bool = True
    router_input: str = "post_attention"
    expert_activation: str = "silu"

    def __post_init__(self) -> None:
        if self.layer_operators is not None:
            # a JSON file gives a list; the config is hashed (jit)
            object.__setattr__(self, "layer_operators",
                               tuple(self.layer_operators))
        _check_block(self)

    @property
    def latent(self) -> bool:
        """The block caches one latent row an attention sub-layer, not a
        K and a V a head."""
        return self.block in _LATENT_SUBLAYERS

    @property
    def attn_sublayers(self) -> int:
        """Attention sub-layers, each with a cache row of its own: the
        layers of the KV pool."""
        if self.layer_operators is not None:
            return sum(POOL_OF[name] != "conv"
                       for name in self.layer_operators)
        return _LATENT_SUBLAYERS.get(self.block, 1) * self.n_layers

    @property
    def window_layers(self) -> int:
        """Layers that name the "window" kind: the layers of the cache's
        second pool (0: the model caches under one table a lane)."""
        return (0 if self.layer_operators is None
                else self.layer_operators.count("window"))

    @property
    def conv_layers(self) -> int:
        """Layers whose operator is the short convolution: the by-slot
        states a step program carries."""
        return (0 if self.layer_operators is None
                else self.layer_operators.count("conv"))

    def operator_index(self, layer_idx: int) -> Tuple[str, int]:
        """Layer ``layer_idx``'s operator and its place among the layers
        that keep what it keeps (``POOL_OF``): an attention layer's layer of
        its kind's pool, a convolution's state."""
        if self.layer_operators is None:
            return "attention", layer_idx
        name = self.layer_operators[layer_idx]
        return name, sum(POOL_OF[other] == POOL_OF[name]
                         for other in self.layer_operators[:layer_idx])

    @property
    def expert_layers(self) -> int:
        """Routed expert layers a forward pass runs (ops/moe.py
        routed_experts_apply; a ``moe_every`` mixture is not one)."""
        if self.block != "gqa_moe" and not self.latent:
            return 0
        return self.n_layers - self.first_dense_layers

    @property
    def routed(self) -> bool:
        """The block has a routed expert layer: its step programs return
        routing counts beside their tokens."""
        return self.expert_layers > 0

    @property
    def held_experts(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else self.experts_held)

    def layer_is_moe(self, layer_idx: int) -> bool:
        return (self.moe_every is not None
                and layer_idx % self.moe_every == self.moe_every - 1)

    @property
    def head_dim(self) -> int:
        if self.head_width is not None:
            return self.head_width
        return self.d_model // self.n_heads

    def transfer_counts(self) -> Tuple[int, ...]:
        """Rows a diffusion block commits at each of its denoising
        passes: ``diffusion_block`` spread over ``diffusion_steps``, the
        remainder to the first passes (a pass commits fewer where fewer
        rows are still masked)."""
        base, extra = divmod(self.diffusion_block, self.diffusion_steps)
        return tuple(base + (i < extra)
                     for i in range(self.diffusion_steps))

    @property
    def kv_heads(self) -> int:
        """KV head count: n_kv_heads (GQA/MQA) or n_heads (MHA)."""
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads


# attention sub-layers a layer of each latent kind
_LATENT_SUBLAYERS = {"latent_shortcut": 2, "latent_moe": 1}


def _check_routed(config: TransformerConfig) -> None:
    """What every block with the routed expert layer needs of it."""
    for name in ("n_routed_experts", "router_top_k", "expert_d_ff"):
        if getattr(config, name) < 1:
            raise ValueError(
                f"block {config.block!r} needs {name} >= 1, got "
                f"{getattr(config, name)}")
    # a window is a layer KIND's ('gqa_moe' layers that name "window":
    # _check_operators), never the whole block's
    if config.moe_every is not None or config.positional != "rope" or (
            config.attention_window is not None
            and not config.window_layers):
        raise ValueError(
            f"block {config.block!r} takes neither moe_every nor "
            f"attention_window, and positional='rope' (it has no learned "
            f"positions)")
    if config.router_scoring not in ("softmax", "sigmoid"):
        raise ValueError(
            f"router_scoring must be 'softmax' or 'sigmoid', got "
            f"{config.router_scoring!r}")
    total = config.n_routed_experts + config.n_zero_experts
    if not 1 <= config.router_top_k <= total:
        raise ValueError(
            f"router_top_k must be in [1, {total}], got "
            f"{config.router_top_k}")
    last = config.first_expert_held + config.held_experts
    if config.first_expert_held < 0 or config.held_experts < 1 \
            or last > config.n_routed_experts:
        raise ValueError(
            f"experts held [{config.first_expert_held}, {last}) are not "
            f"among the {config.n_routed_experts} routed experts")


def _check_gqa_moe(config: TransformerConfig) -> None:
    _check_routed(config)
    if config.head_dim < 2 or config.head_dim % 2:
        raise ValueError(
            f"head_width must be even and >= 2, got {config.head_dim}")
    if config.kv_heads < 1 or config.n_heads % config.kv_heads:
        raise ValueError(
            f"n_heads ({config.n_heads}) must be a multiple of n_kv_heads "
            f"({config.kv_heads})")
    if config.n_zero_experts or config.n_shared_experts:
        raise ValueError(
            "block 'gqa_moe' has the routed experts alone as a layer's "
            "feed-forward: no zero-compute or shared expert (the latent "
            "blocks')")
    if not 0 <= config.first_dense_layers < config.n_layers \
            or (config.first_dense_layers and config.d_ff < 1):
        raise ValueError(
            f"first_dense_layers must be in [0, {config.n_layers}) (the "
            f"last layer is an expert layer) with d_ff >= 1 the dense "
            f"width, got {config.first_dense_layers} and {config.d_ff}")
    _check_operators(config)
    if not config.qk_norm and config.layer_operators is None:
        raise ValueError(
            "qk_norm=False is served where the layers name their operator "
            "(the projections held as matrices: retention_qkv)")
    b, steps = config.diffusion_block, config.diffusion_steps
    if b < 0 or (b == 0 and (steps or config.mask_token)):
        raise ValueError(
            f"diffusion_block must be >= 0, and diffusion_steps and "
            f"mask_token mean nothing without it; got {b}, {steps}, "
            f"{config.mask_token}")
    if b and not 1 <= steps <= b:
        raise ValueError(
            f"diffusion_steps must be in [1, diffusion_block = {b}] (a "
            f"pass commits at least one row), got {steps}")
    if b and not 0 <= config.mask_token < config.vocab_size:
        raise ValueError(
            f"mask_token {config.mask_token} is not among the "
            f"{config.vocab_size} ids")


# an operator a layer may name -> what keeps its rows: the pool of the
# full kind (every row of a request, under one table), the pool of the
# window kind (the rows a window still reaches), or a state by slot
POOL_OF = {"attention": "full", "global": "full", "window": "window",
           "conv": "conv"}
OPERATORS = tuple(POOL_OF)


def _check_operators(config: TransformerConfig) -> None:
    """``layer_operators``, where a model's layers name their operator."""
    ops, taps = config.layer_operators, config.conv_taps
    if ops is None:
        if taps:
            raise ValueError(
                f"conv_taps {taps} means nothing without layer_operators")
        return
    if len(ops) != config.n_layers or set(ops) - set(OPERATORS):
        raise ValueError(
            f"layer_operators names one of {OPERATORS} a layer, "
            f"{config.n_layers} in all, got {ops!r}")
    if not any(POOL_OF[name] == "full" for name in ops):
        raise ValueError(
            "layer_operators names no 'attention' or 'global' layer: the "
            "paged pool would hold nothing")
    window = config.attention_window
    if ("window" in ops) != (window is not None) or (
            window is not None and window < 1):
        raise ValueError(
            f"attention_window must be >= 1 where a layer's operator is "
            f"'window' and None where none is, got {window}")
    if "window" in ops and "conv" in ops:
        raise ValueError(
            "layer_operators names both 'window' and 'conv': a cache by "
            "layer kind beside a state by slot is not served")
    if ("conv" in ops) != (taps >= 2):
        raise ValueError(
            f"conv_taps must be >= 2 where a layer's operator is 'conv' "
            f"and 0 where none is, got {taps}")
    if config.diffusion_block:
        raise ValueError(
            "layer_operators is served under the causal mask only: a "
            "convolution's state has no block to see both ways, and a "
            "window cuts through one")


def _check_retention(config: TransformerConfig) -> None:
    if config.head_dim < 2 or config.head_dim % 2:
        raise ValueError(
            f"head_width must be even and >= 2, got {config.head_dim}")
    if config.kv_heads < 1 or config.n_heads % config.kv_heads:
        raise ValueError(
            f"n_heads ({config.n_heads}) must be a multiple of n_kv_heads "
            f"({config.kv_heads})")
    if config.moe_every is not None or config.attention_window is not None \
            or config.positional != "rope" or config.d_ff < 1:
        raise ValueError(
            "block 'retention' takes neither moe_every nor "
            "attention_window, positional='rope', and a gated FFN of "
            "width d_ff >= 1 in every layer")


def _check_block(config: TransformerConfig) -> None:
    if config.router_input not in ("post_attention", "layer_input") \
            or config.expert_activation not in ("silu", "relu"):
        raise ValueError(
            f"router_input must be 'post_attention' or 'layer_input' and "
            f"expert_activation 'silu' or 'relu', got "
            f"{config.router_input!r} and {config.expert_activation!r}")
    if config.block == "gqa_moe":
        return _check_gqa_moe(config)
    if not config.qk_norm or config.router_input != "post_attention" \
            or config.expert_activation != "silu":
        raise ValueError(
            f"qk_norm, router_input and expert_activation are block "
            f"'gqa_moe''s; block {config.block!r} takes none of them")
    if config.block not in ("dense", "retention") and not config.latent:
        raise ValueError(
            f"block must be 'dense', 'gqa_moe', 'retention', "
            f"'latent_shortcut' or 'latent_moe', got {config.block!r}")
    if config.diffusion_block or config.diffusion_steps \
            or config.mask_token:
        raise ValueError(
            f"diffusion_block, diffusion_steps and mask_token are block "
            f"'gqa_moe''s; block {config.block!r} takes none of them")
    if config.block == "retention":
        if config.layer_operators is not None or config.conv_taps:
            raise ValueError(
                "layer_operators and conv_taps are block 'gqa_moe''s; "
                "block 'retention' takes neither")
        return _check_retention(config)
    if config.head_width is not None:
        raise ValueError(
            f"head_width is block 'gqa_moe''s and 'retention''s; block "
            f"{config.block!r} does not take it")
    if config.layer_operators is not None or config.conv_taps:
        raise ValueError(
            f"layer_operators and conv_taps are block 'gqa_moe''s; block "
            f"{config.block!r} takes neither")
    if not config.latent:
        if config.rope_theta != 10000.0 or config.norm_eps != 1e-6:
            raise ValueError(
                "the dense block's rotary base (10000) and norm epsilon "
                "(1e-6) are fixed; rope_theta and norm_eps are the "
                "latent blocks' and 'gqa_moe''s")
        return
    for name in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                 "qk_rope_head_dim", "v_head_dim"):
        if getattr(config, name) < 1:
            raise ValueError(
                f"block {config.block!r} needs {name} >= 1, got "
                f"{getattr(config, name)}")
    if config.qk_rope_head_dim % 2:
        raise ValueError("qk_rope_head_dim must be even")
    _check_routed(config)
    single = config.block == "latent_moe"
    if config.n_shared_experts < 0 or not (
            0 <= config.first_dense_layers <= config.n_layers):
        raise ValueError(
            f"n_shared_experts must be >= 0 and first_dense_layers in "
            f"[0, {config.n_layers}], got {config.n_shared_experts} and "
            f"{config.first_dense_layers}")
    if not single and (config.n_shared_experts or config.first_dense_layers):
        raise ValueError(
            "n_shared_experts and first_dense_layers are block "
            "'latent_moe''s: a 'latent_shortcut' double layer has two "
            "dense FFNs and one expert layer, every layer alike")


def _latent_layer_init(keys, config: TransformerConfig, dense,
                       layer_idx: int) -> Dict:
    """One layer of a latent block: a 'latent_shortcut' double layer, or
    a 'latent_moe' single layer (dense or routed, by its place)."""
    d, h, f = config.d_model, config.n_heads, config.d_ff
    qr, kr = config.q_lora_rank, config.kv_lora_rank
    nope, rope, vd = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                      config.v_head_dim)
    e, fe = config.held_experts, config.expert_d_ff
    outputs = config.n_routed_experts + config.n_zero_experts

    def attn():
        return {
            "wdq": dense(next(keys), (d, qr), d),
            "q_norm": {"scale": jnp.ones((qr,))},
            "wuq": dense(next(keys), (qr, h, nope + rope), qr),
            "wdkv": dense(next(keys), (d, kr + rope), d),
            "kv_norm": {"scale": jnp.ones((kr,))},
            # per head [k_nope | v]
            "wukv": dense(next(keys), (kr, h, nope + vd), kr),
            "wo": dense(next(keys), (h, vd, d), h * vd),
        }

    def ffn(width):
        return {"w_gate": dense(next(keys), (d, width), d),
                "w_up": dense(next(keys), (d, width), d),
                "w_down": dense(next(keys), (width, d), width)}

    def moe():
        out = {"router": dense(next(keys), (d, outputs), d),
               "w_gate": dense(next(keys), (e, d, fe), d),
               "w_up": dense(next(keys), (e, d, fe), d),
               "w_down": dense(next(keys), (e, fe, d), fe)}
        if config.router_choice_bias:
            # what a load balancer would move; a fresh model's is 0
            out["bias"] = jnp.zeros((outputs,))
        return out

    norm = lambda: {"scale": jnp.ones((d,))}
    if config.block == "latent_shortcut":
        return {
            "attn": [attn(), attn()],
            "norm_attn": [norm(), norm()],
            "norm_ffn": [norm(), norm()],
            "ffn": [ffn(f), ffn(f)],
            "moe": moe(),
        }
    layer = {"attn": attn(), "norm_attn": norm(), "norm_ffn": norm()}
    if layer_idx < config.first_dense_layers:
        layer["ffn"] = ffn(f)
        return layer
    layer["moe"] = moe()
    if config.n_shared_experts:
        layer["shared"] = ffn(config.n_shared_experts * fe)
    return layer


def _gqa_moe_layer_init(keys, config: TransformerConfig, dense,
                        layer_idx: int) -> Dict:
    """One 'gqa_moe' layer: its operator — the dense block's attention
    at the explicit head width with its two per-head norms or, where the
    layer names it, the short convolution (``w_in`` [d, 3 d] = [B | C |
    u], ``filter`` [taps, d], ``w_out`` [d, d]: ops/short_conv.py) — two
    norms, and its feed-forward: the routed experts, all held, or a dense
    gated FFN in the first ``first_dense_layers`` layers."""
    d, h, h_kv, hd = (config.d_model, config.n_heads, config.kv_heads,
                      config.head_dim)
    e, fe = config.held_experts, config.expert_d_ff
    layer = {"norm1": {"scale": jnp.ones((d,))},
             "norm2": {"scale": jnp.ones((d,))}}
    if config.operator_index(layer_idx)[0] == "conv":
        taps = config.conv_taps
        layer["conv"] = {"w_in": dense(next(keys), (d, 3 * d), d),
                         "filter": dense(next(keys), (taps, d), taps),
                         "w_out": dense(next(keys), (d, d), d)}
    else:
        # where the layers name their operator the three input projections
        # are held as MATRICES [d, heads x hd], as the 'retention' block's:
        # a [d, 32, 64] array's own layout pads each head's 64 values to a
        # whole 128-lane register
        heads = ((lambda n: (d, n * hd)) if config.layer_operators
                 else (lambda n: (d, n, hd)))
        layer["attn"] = {"wq": dense(next(keys), heads(h), d),
                         "wk": dense(next(keys), heads(h_kv), d),
                         "wv": dense(next(keys), heads(h_kv), d),
                         "wo": dense(next(keys), (h, hd, d), h * hd)}
        if config.qk_norm:
            layer["attn"].update(q_norm={"scale": jnp.ones((hd,))},
                                 k_norm={"scale": jnp.ones((hd,))})
    if layer_idx < config.first_dense_layers:
        f = config.d_ff
        layer["ffn"] = {"w_gate": dense(next(keys), (d, f), d),
                        "w_up": dense(next(keys), (d, f), d),
                        "w_down": dense(next(keys), (f, d), f)}
        return layer
    layer["moe"] = {"router": dense(next(keys),
                                    (d, config.n_routed_experts), d),
                    "w_gate": dense(next(keys), (e, d, fe), d),
                    "w_up": dense(next(keys), (e, d, fe), d),
                    "w_down": dense(next(keys), (e, fe, d), fe)}
    if config.router_choice_bias:
        layer["moe"]["bias"] = jnp.zeros((config.n_routed_experts,))
    return layer


# the log gate a fresh 'retention' model starts from: a row keeps
# sigmoid(GATE_BIAS + N(0, 1)) of what came before, about e^(-1/400)
GATE_BIAS = 6.0


def _retention_layer_init(keys, config: TransformerConfig, dense,
                          layer_idx: int) -> Dict:
    """One 'retention' layer: 'gqa_moe''s attention matrices and per-head
    norms, the gate's map to one logit a KV head (WITH a bias), two
    norms and a dense gated FFN.  The three input projections are held
    as MATRICES ``[d, heads x hd]``: a ``[d, 40, hd]`` array's own layout
    pads 40 heads to 48, so the TPU compiler re-lays every one of them out
    on every dispatch (72 MB a layer at the cell's size)."""
    d, h, h_kv, hd, f = (config.d_model, config.n_heads, config.kv_heads,
                         config.head_dim, config.d_ff)
    attn = {"wq": dense(next(keys), (d, h * hd), d),
            "wk": dense(next(keys), (d, h_kv * hd), d),
            "wv": dense(next(keys), (d, h_kv * hd), d),
            "wo": dense(next(keys), (h, hd, d), h * hd),
            "q_norm": {"scale": jnp.ones((hd,))},
            "k_norm": {"scale": jnp.ones((hd,))},
            "gate": {"w": dense(next(keys), (d, h_kv), d),
                     "b": jnp.full((h_kv,), GATE_BIAS)}}
    return {"attn": attn,
            "norm1": {"scale": jnp.ones((d,))},
            "norm2": {"scale": jnp.ones((d,))},
            "ffn": {"w_gate": dense(next(keys), (d, f), d),
                    "w_up": dense(next(keys), (d, f), d),
                    "w_down": dense(next(keys), (f, d), f)}}


_LAYER_INIT = {"latent_shortcut": _latent_layer_init,
               "latent_moe": _latent_layer_init,
               "gqa_moe": _gqa_moe_layer_init,
               "retention": _retention_layer_init}


def transformer_init(rng: jax.Array, config: TransformerConfig) -> Dict:
    if config.block in _LAYER_INIT:
        def dense(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (1.0 / fan_in) ** 0.5)

        layer_init = _LAYER_INIT[config.block]
        keys = iter(jax.random.split(rng, 2 + 20 * config.n_layers))
        d = config.d_model
        return {
            "embed": dense(next(keys), (config.vocab_size, d), d),
            "layers": [layer_init(keys, config, dense, i)
                       for i in range(config.n_layers)],
            "final_norm": {"scale": jnp.ones((d,))},
            "lm_head": dense(next(keys), (d, config.vocab_size), d),
        }
    if config.moe_every is not None and config.moe_every < 1:
        raise ValueError(f"moe_every must be >= 1, got {config.moe_every}")
    if config.kv_heads < 1:
        raise ValueError(f"n_kv_heads must be >= 1, got {config.kv_heads}")
    if config.n_heads % config.kv_heads != 0:
        raise ValueError(
            f"n_heads ({config.n_heads}) must be a multiple of n_kv_heads "
            f"({config.kv_heads})"
        )
    n = 4 + 7 * config.n_layers
    keys = iter(jax.random.split(rng, n))
    d, h, f = config.d_model, config.n_heads, config.d_ff
    h_kv = config.kv_heads
    hd = config.head_dim

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * (1.0 / fan_in) ** 0.5

    if config.positional not in ("learned", "rope"):
        raise ValueError(
            f"positional must be 'learned' or 'rope', got {config.positional!r}"
        )
    params: Dict = {
        "embed": dense(next(keys), (config.vocab_size, d), d),
        "layers": [],
        "final_norm": {"scale": jnp.ones((d,))},
        "lm_head": dense(next(keys), (d, config.vocab_size), d),
    }
    if config.positional == "learned":
        # rope configs skip the table entirely (at long max_seq_len it would
        # be dead weight in params, optimizer state, and checkpoints)
        params["pos_embed"] = dense(next(keys), (config.max_seq_len, d), d)
    for i in range(config.n_layers):
        layer = {
            "attn": {
                "wq": dense(next(keys), (d, h, hd), d),
                "wk": dense(next(keys), (d, h_kv, hd), d),
                "wv": dense(next(keys), (d, h_kv, hd), d),
                "wo": dense(next(keys), (h, hd, d), d),
            },
            "norm1": {"scale": jnp.ones((d,))},
            "norm2": {"scale": jnp.ones((d,))},
        }
        if config.layer_is_moe(i):
            from ..ops.moe import MoEConfig, moe_init

            layer["moe"] = moe_init(
                next(keys),
                MoEConfig(d_model=d, d_ff=f,
                          num_experts=config.moe_num_experts,
                          capacity_factor=config.moe_capacity_factor,
                          top_k=config.moe_top_k,
                          routing=config.moe_routing),
            )
        else:
            layer["mlp"] = {
                "w_in": dense(next(keys), (d, f), d),
                "w_out": dense(next(keys), (f, d), f),
            }
        params["layers"].append(layer)
    return params


def _rms_norm(x, scale, eps: float = 1e-6):
    norm = jax.lax.rsqrt(jnp.mean(x.astype(jnp.float32) ** 2, -1, keepdims=True) + eps)
    return (x * norm.astype(x.dtype)) * scale.astype(x.dtype)


# ---------------------------------------------------------------------------
# the latent blocks: latent (MLA) attention, gated FFNs and a routed expert
# layer, put together as a double layer with a shortcut ('latent_shortcut')
# or as single layers whose feed-forward is dense or routed by their place
# ('latent_moe').  These pieces are shared by the unpaged forward below and
# the paged step programs (serving/paged.py); only the cache plumbing
# differs.
# ---------------------------------------------------------------------------

@jax.named_scope("mla")
def latent_qkv(attn, y, positions, config: TransformerConfig):
    """One sub-layer's projections of ``y`` [B, C, d] at ``positions``
    [B, C]: ``q_nope`` [B, H, C, nope], ``q_rope`` [B, H, C, rope]
    (rotated), and the two parts of the row the sub-layer caches —
    ``c_kv`` [B, C, kv_lora_rank] (normed, and scaled where the model
    has ``mla_rank_scaling``) and ``k_rope`` [B, C, rope] (rotated; ONE
    key for all heads)."""
    dtype = config.dtype
    d, eps = config.d_model, config.norm_eps
    kr, nope = config.kv_lora_rank, config.qk_nope_head_dim
    c_q = _rms_norm(y @ attn["wdq"].astype(dtype),
                    attn["q_norm"]["scale"], eps)
    q = jnp.einsum("bcr,rhk->bhck", c_q, attn["wuq"].astype(dtype))
    a = y @ attn["wdkv"].astype(dtype)
    c_kv = _rms_norm(a[..., :kr], attn["kv_norm"]["scale"], eps)
    if config.mla_rank_scaling:
        q = q * jnp.asarray((d / config.q_lora_rank) ** 0.5, dtype)
        c_kv = c_kv * jnp.asarray((d / kr) ** 0.5, dtype)
    rope = lambda x: apply_rope(x, positions, theta=config.rope_theta,
                                interleaved=True)
    k_rope = rope(a[:, None, :, kr:])[:, 0]
    return q[..., :nope], rope(q[..., nope:]), c_kv, k_rope


def _latent_softmax(scores, positions, dtype):
    """Causal softmax of ``scores`` [B, H, C, V] (float32): query i of
    lane b sees view rows ``<= positions[b, i]``.

    The row maximum goes through an optimization barrier: fused with its
    broadcast, the TPU compiler turns it into a reduce-window as wide as
    the view (16383 for 8192 rows), which took 19 of the 25 ms a block
    of 128 queries cost on a v5e (PERF.md, PR 27)."""
    k_pos = jnp.arange(scores.shape[-1])
    valid = k_pos[None, None, :] <= positions[:, :, None]  # [B, C, V]
    scores = jnp.where(valid[:, None], scores, -jnp.inf)
    top = jax.lax.optimization_barrier(
        jnp.max(scores, axis=-1, keepdims=True))
    weights = jnp.exp(scores - top)
    return (weights / jnp.sum(weights, axis=-1, keepdims=True)).astype(dtype)


@jax.named_scope("mla")
def latent_attend(attn, q_nope, q_rope, view_c, view_r, positions,
                  config: TransformerConfig, absorbed: bool):
    """Attention of one sub-layer over latent rows ``view_c`` [B, V,
    kv_lora_rank] / ``view_r`` [B, V, rope] -> [B, C, d].

    ``absorbed``: the scores and the context are taken over the latent
    rows themselves (the key up-projection folded into the query, the
    value up-projection applied to the attended latent) — nothing of
    ``V x heads`` is ever built, which is what a decode step over a
    long view needs.  Otherwise every view row is expanded to a key and
    a value a head first: fewer FLOPs a query when there are many
    queries (a prefill chunk).  Both are the same numbers."""
    dtype = config.dtype
    nope = config.qk_nope_head_dim
    scale = latent_scale(config)
    f32 = jnp.float32
    rope_scores = jnp.einsum("bhce,bve->bhcv", q_rope, view_r,
                             preferred_element_type=f32)
    if absorbed:
        def attend(q_abs):
            scores = rope_scores + jnp.einsum(
                "bhcr,bvr->bhcv", q_abs, view_c, preferred_element_type=f32)
            probs = _latent_softmax(scores * scale, positions, dtype)
            return jnp.einsum("bhcv,bvr->bhcr", probs, view_c)

        return latent_absorbed(attn, q_nope, attend, config)
    wukv = attn["wukv"].astype(dtype)
    k_nope = jnp.einsum("bvr,rhn->bhvn", view_c, wukv[..., :nope])
    v = jnp.einsum("bvr,rhm->bhvm", view_c, wukv[..., nope:])
    scores = rope_scores + jnp.einsum("bhcn,bhvn->bhcv", q_nope, k_nope,
                                      preferred_element_type=f32)
    probs = _latent_softmax(scores * scale, positions, dtype)
    o = jnp.einsum("bhcv,bhvm->bhcm", probs, v)
    return jnp.einsum("bhcm,hmd->bcd", o, attn["wo"].astype(dtype))


def attend_key_blocks(view_block, block_rows: int, scores_of, context_of,
                      positions, heads, width: int, window=None):
    """Causal softmax attention over a view that is handed over a block
    of K = ``block_rows`` rows at a time — the one running softmax of the
    paged steps (:func:`latent_attend_blocks`, ``decoding._attend_blocks``).

    ``view_block(i)`` gives rows ``[i * K, (i + 1) * K)`` of every lane's
    view, as whatever tuple the two closures take: ``scores_of(*view)``
    the scaled float32 scores [B, *heads, C, K] and
    ``context_of(weights, *view)`` the float32 product [B, *heads, C,
    width] of the float32 weights (which it casts to the served dtype
    first) with the block's values.  Query i of lane b sits at
    ``positions[b, i]`` and sees rows at or before it (and, with a
    ``window``, fewer than ``window`` rows back).  Only the blocks that
    hold a row some query may see are asked for — up to the largest of
    ``positions`` and, under a ``window``, from the block that holds the
    first row the step's earliest query still reaches (``min(positions) -
    window + 1``: a 512-row chunk at row 12,288 under a window of 4,096
    walks 9 blocks of 512, not 25) — and the softmax is carried across them
    in float32
    (running maximum, running sum, rescaled context), so a step's
    attention costs what its lanes hold, not what a lane may hold.
    Returns the normalised context, float32: the numbers of the whole
    view at once, up to the order of the sums.

    A block past a lane's reach is an exact no-op for that lane (scores
    ``-inf``, so the maximum stays, ``keep = exp(0) = 1`` and the weights
    are 0): a lane's numbers do not depend on how far its neighbours
    reach.  The running maximum goes through an optimization barrier
    (see :func:`_latent_softmax`: fused with its broadcast it became a
    reduce-window as wide as the view, 817 ms a dispatch; PERF.md, PR 27)."""
    b, c = positions.shape
    lead = (b, *heads, c)
    f32 = jnp.float32

    def step(i, carry):
        top, total, ctx = carry
        view = view_block(i)
        scores = scores_of(*view)
        k_pos = i * block_rows + jnp.arange(block_rows)
        valid = k_pos[None, None, :] <= positions[:, :, None]  # [B, C, K]
        if window is not None:
            valid = valid & (
                positions[:, :, None] - k_pos[None, None, :] < window)
        valid = jnp.expand_dims(valid, tuple(range(1, 1 + len(heads))))
        scores = jnp.where(valid, scores, -jnp.inf)
        new_top = jax.lax.optimization_barrier(
            jnp.maximum(top, jnp.max(scores, axis=-1)))
        weights = jnp.exp(scores - new_top[..., None])
        keep = jnp.exp(top - new_top)
        total = total * keep + jnp.sum(weights, axis=-1)
        ctx = ctx * keep[..., None] + context_of(weights, *view)
        return new_top, total, ctx

    # Without a window block 0 holds row 0, which every query sees: the
    # maximum is finite from the first step on.  Under a window a leading
    # block can be wholly masked for a query, and -inf - (-inf) is NaN:
    # there the maximum starts from a finite floor no score reaches.
    floor = -jnp.inf if window is None else jnp.finfo(f32).min / 2
    first = 0 if window is None else jnp.maximum(
        jnp.min(positions) - window + 1, 0) // block_rows
    _, total, ctx = jax.lax.fori_loop(
        first, jnp.max(positions) // block_rows + 1, step,
        (jnp.full(lead, floor, f32), jnp.zeros(lead, f32),
         jnp.zeros((*lead, width), f32)))
    return ctx / total[..., None]


def latent_scale(config: TransformerConfig) -> float:
    """What the latent blocks' scores are multiplied by."""
    return (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5


@jax.named_scope("mla")
def latent_absorbed(attn, q_nope, attend, config: TransformerConfig):
    """The absorbed form around the attention itself: the key
    up-projection folded into the query (``q_abs`` [B, H, C,
    kv_lora_rank]), ``attend(q_abs)`` the normalised context over the
    latent rows [B, H, C, kv_lora_rank], the value up-projection and the
    output projection applied to it -> [B, C, d].  Where the paged steps'
    two ways through a view meet: the key-block loop
    (:func:`latent_attend_blocks`) and the paged kernel
    (``ops/paged_attention.paged_latent_decode_attention``)."""
    dtype = config.dtype
    nope = config.qk_nope_head_dim
    wukv = attn["wukv"].astype(dtype)
    wuk, wuv = wukv[..., :nope], wukv[..., nope:]
    q_abs = jnp.einsum("bhcn,rhn->bhcr", q_nope, wuk)
    ctx = attend(q_abs).astype(dtype)
    o = jnp.einsum("bhcr,rhm->bhcm", ctx, wuv)
    return jnp.einsum("bhcm,hmd->bcd", o, attn["wo"].astype(dtype))


def latent_context_blocks(q_abs, q_rope, view_block, block_rows: int,
                          positions, config: TransformerConfig):
    """The normalised context [B, H, C, kv_lora_rank] (float32) of absorbed
    queries ``q_abs`` [B, H, C, kv_lora_rank] / ``q_rope`` over a view that
    is handed over a block of K = ``block_rows`` rows at a time:
    ``view_block(i)`` gives rows ``[i * K, (i + 1) * K)`` of every lane's
    view as (``view_c`` [B, K, kv_lora_rank], ``view_r`` [B, K, rope]),
    attended through :func:`attend_key_blocks` as far as the lanes
    reach: what :func:`latent_absorbed` takes as its ``attend``."""
    scale = latent_scale(config)
    f32 = jnp.float32

    def scores_of(view_c, view_r):
        return (jnp.einsum("bhcr,bvr->bhcv", q_abs, view_c,
                           preferred_element_type=f32)
                + jnp.einsum("bhce,bve->bhcv", q_rope, view_r,
                             preferred_element_type=f32)) * scale

    def context_of(weights, view_c, _):
        return jnp.einsum("bhcv,bvr->bhcr", weights.astype(config.dtype),
                          view_c, preferred_element_type=f32)

    return attend_key_blocks(
        view_block, block_rows, scores_of, context_of, positions,
        q_abs.shape[1:2], q_abs.shape[3])


def latent_attend_blocks(attn, q_nope, q_rope, view_block, block_rows: int,
                         positions, config: TransformerConfig):
    """:func:`latent_attend` in the absorbed form over a view that is
    handed over a key block at a time (:func:`latent_context_blocks`).
    Same numbers as the whole view at once, up to the order of the
    sums."""
    return latent_absorbed(
        attn, q_nope,
        lambda q_abs: latent_context_blocks(
            q_abs, q_rope, view_block, block_rows, positions, config),
        config)


def gated_ffn(ffn, y, dtype):
    """SwiGLU: ``(silu(y Wg) * (y Wu)) Wd``."""
    hidden = jax.nn.silu(y @ ffn["w_gate"].astype(dtype)) \
        * (y @ ffn["w_up"].astype(dtype))
    return hidden @ ffn["w_down"].astype(dtype)


def _router_law(config: TransformerConfig) -> Dict:
    """The router's law as ops/moe.py takes it."""
    return dict(top_k=config.router_top_k,
                scale=config.routed_scaling_factor,
                scoring=config.router_scoring,
                renormalise=config.router_renormalise,
                renormalise_eps=config.router_renormalise_eps)


def routed_experts(moe, config: TransformerConfig, y, live=None,
                   choices=None):
    """A layer's routed experts over ``y`` [B, C, d] -> (out [B, C, d],
    routing counts int32[6]: see ops/moe.py).  A row that ``live``
    [B, C] says is dead (an idle lane, a chunk's padding) chooses
    nothing.  ``choices``: the rows' experts and weights where the router
    read something else than ``y`` (ops/moe.py ``route``; None: it reads
    ``y``).  The tiles run as one Pallas kernel where the backend the
    program is being built for can run one and the experts' shapes fit
    it (ops/moe.py ``expert_path``; ``serving.paged._kernel_mode`` is
    the one place that says how a kernel can run, the attention's and
    this one), else as a loop."""
    from ..ops.moe import routed_experts_apply
    from ..serving.paged import _kernel_mode

    b, c, d = y.shape
    out, counts = routed_experts_apply(
        moe, y.reshape(b * c, d),
        n_routed=config.n_routed_experts,
        first_held=config.first_expert_held,
        live=None if live is None else live.reshape(b * c),
        kernel_mode=_kernel_mode(), choices=choices,
        activation=config.expert_activation, **_router_law(config))
    return out.reshape(b, c, d), counts


def _shortcut_layer(layer, x, config: TransformerConfig, attend, live):
    """One 'latent_shortcut' double layer."""
    dtype, eps = config.dtype, config.norm_eps
    expert_out = counts = None
    for j in range(2):
        y = _rms_norm(x, layer["norm_attn"][j]["scale"], eps)
        x = x + attend(j, layer["attn"][j], y)
        y = _rms_norm(x, layer["norm_ffn"][j]["scale"], eps)
        if j == 0:
            # the shortcut: routed on the first sub-layer's normed
            # hidden state, added after the second sub-layer's FFN
            expert_out, counts = routed_experts(layer["moe"], config, y,
                                                live)
        with jax.named_scope("ffn"):
            x = x + gated_ffn(layer["ffn"][j], y, dtype)
    return x + expert_out, counts


def _single_layer(layer, x, config: TransformerConfig, attend, live):
    """One 'latent_moe' layer: the attention, then the feed-forward the
    layer holds — a dense FFN, or the routed experts beside the shared
    expert, which runs once over every row, outside the routed loop."""
    dtype, eps = config.dtype, config.norm_eps
    x = x + attend(0, layer["attn"],
                   _rms_norm(x, layer["norm_attn"]["scale"], eps))
    y = _rms_norm(x, layer["norm_ffn"]["scale"], eps)
    if "moe" not in layer:
        with jax.named_scope("dense_ffn"):
            return x + gated_ffn(layer["ffn"], y, dtype), None
    out, counts = routed_experts(layer["moe"], config, y, live)
    if "shared" in layer:
        with jax.named_scope("shared_expert"):
            out = out + gated_ffn(layer["shared"], y, dtype)
    return x + out, counts


_LATENT_LAYER = {"latent_shortcut": _shortcut_layer,
                 "latent_moe": _single_layer}


def latent_layers(params, x, config: TransformerConfig, attend_row,
                  live=None):
    """Every layer of a latent block over ``x`` [B, C, d].
    ``attend_row(row, attn_weights, y)`` is the attention of the normed
    input ``y`` by the sub-layer whose cache row is ``row`` (0 ..
    ``attn_sublayers - 1``) — where the callers differ: the unpaged
    forward attends its own rows, a cached step writes the row and
    attends the lane's view.  ``live`` [B, C] says which rows are real
    (None: all).  Returns (x, routing counts int32[6] summed over the
    expert layers: ops/moe.py)."""
    from ..ops.moe import ROUTING_COUNTS

    layer_fn = _LATENT_LAYER[config.block]
    per_layer = _LATENT_SUBLAYERS[config.block]
    counts = jnp.zeros((len(ROUTING_COUNTS),), jnp.int32)
    for i, layer in enumerate(params["layers"]):
        attend = lambda j, attn, y, first=per_layer * i: attend_row(
            first + j, attn, y)
        x, layer_counts = layer_fn(layer, x, config, attend, live)
        if layer_counts is not None:
            counts = counts + layer_counts
    return x, counts


def _latent_forward(params, tokens, config: TransformerConfig,
                    apply_head: bool = True):
    """The unpaged forward of a latent block: every sub-layer attends
    its own rows in the expanded form."""
    dtype = config.dtype
    b, seq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(seq)[None, :], (b, seq))
    x = params["embed"][tokens].astype(dtype)

    def attend(_, attn, y):
        q_nope, q_rope, c_kv, k_rope = latent_qkv(attn, y, positions, config)
        return latent_attend(attn, q_nope, q_rope, c_kv, k_rope, positions,
                             config, absorbed=False)

    x, _ = latent_layers(params, x, config, attend)
    x = _rms_norm(x, params["final_norm"]["scale"], config.norm_eps)
    if not apply_head:
        return x, jnp.float32(0.0)
    return ((x @ params["lm_head"].astype(dtype)).astype(jnp.float32),
            jnp.float32(0.0))


# ---------------------------------------------------------------------------
# the 'gqa_moe' block: the dense block's K/V-a-head attention at an explicit
# head width with per-head q/k norms, the routed experts as every layer's
# feed-forward, and — where the configuration generates by diffusion over
# blocks — a block-causal mask.  Shared, like the latent pieces, by the
# unpaged forward below and the paged step programs (serving/paged.py).
# ---------------------------------------------------------------------------

def attend_reach(config: TransformerConfig, positions):
    """The last row the query at each of ``positions`` sees: itself
    under the causal mask, the last row of its aligned block of
    ``diffusion_block`` positions under the block-causal one (row i sees
    row j iff ``j // B <= i // B``).  Every cached attention masks by
    ``key position <= reach``, so this is all of the mask."""
    b = config.diffusion_block
    if not b:
        return positions
    return positions // b * b + (b - 1)


@jax.named_scope("attention")
def gqa_qkv(attn, y, positions, config: TransformerConfig,
            rotate: bool = True):
    """A 'gqa_moe' layer's projections of ``y`` [B, C, d] at ``positions``
    [B, C]: ``q`` [B, H, C, hd], ``k`` and ``v`` [B, H_kv, C, hd], q and k
    normed a head and then rotated (split halves).  Weights held as
    matrices ``[d, heads x hd]`` (a model whose layers name their operator)
    go through :func:`retention_qkv`, the same numbers — and only there
    can a model leave the norms out (``qk_norm``) and a layer the rotation
    (``rotate``: the "global" kind's)."""
    if attn["wq"].ndim == 2:
        return retention_qkv(attn, y, positions, config, rotate)
    dtype, eps = config.dtype, config.norm_eps
    q = jnp.einsum("bsd,dhk->bhsk", y, attn["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bhsk", y, attn["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bhsk", y, attn["wv"].astype(dtype))
    with jax.named_scope("qk_norm"):
        q = _rms_norm(q, attn["q_norm"]["scale"], eps)
        k = _rms_norm(k, attn["k_norm"]["scale"], eps)
    return (apply_rope(q, positions, theta=config.rope_theta),
            apply_rope(k, positions, theta=config.rope_theta), v)


def gqa_moe_layers(params, x, config: TransformerConfig, attend_row,
                   live=None, conv_row=None):
    """Every layer of a 'gqa_moe' block over ``x`` [B, C, d], each by the
    operator it names.  ``attend_row(row, attn_weights, y)`` is the
    attention's context of the normed input ``y``, [B, H, C, hd] before the
    output projection, by the layer whose cache row is pool layer ``row``;
    ``conv_row(state, conv_weights, y)`` the short convolution's output
    [B, C, d] by the layer whose state is the ``state``-th — where the
    callers differ, as in :func:`latent_layers`: the unpaged forward attends
    its own rows and convolves from zeros, a cached step writes the row and
    attends the lane's view, reads the lane's state and leaves the new one.
    ``attend_row`` also hears the operator the layer names ("attention",
    "global", "window": what it rotates, what it sees and which pool keeps
    its rows).  Returns (x, routing counts int32[6] summed over the expert
    layers: ops/moe.py)."""
    from ..ops.moe import ROUTING_COUNTS, route

    dtype, eps = config.dtype, config.norm_eps
    counts = jnp.zeros((len(ROUTING_COUNTS),), jnp.int32)
    early = config.router_input == "layer_input"
    for i, layer in enumerate(params["layers"]):
        operator, index = config.operator_index(i)
        # a router that reads the layer's input chooses before the operator
        choices = (route(layer["moe"], x.reshape(-1, x.shape[-1]),
                         **_router_law(config))
                   if early and "moe" in layer else None)
        y = _rms_norm(x, layer["norm1"]["scale"], eps)
        if operator == "conv":
            x = x + conv_row(index, layer["conv"], y)
        else:
            o = attend_row(index, layer["attn"], y, operator).astype(dtype)
            with jax.named_scope("attention"):
                x = x + jnp.einsum("bhsk,hkd->bsd", o,
                                   layer["attn"]["wo"].astype(dtype))
        y = _rms_norm(x, layer["norm2"]["scale"], eps)
        if "moe" in layer:
            out, layer_counts = routed_experts(layer["moe"], config, y, live,
                                               choices)
            x = x + out
            counts = counts + layer_counts
        else:
            with jax.named_scope("dense_ffn"):
                x = x + gated_ffn(layer["ffn"], y, dtype)
    return x, counts


def _gqa_moe_forward(params, tokens, config: TransformerConfig,
                     apply_head: bool = True):
    """The unpaged forward of a 'gqa_moe' block: every layer attends its
    own rows, each as far as :func:`attend_reach` says (a layer that names
    the short convolution convolves from zeros)."""
    from ..ops.short_conv import short_conv

    dtype = config.dtype
    b, seq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(seq)[None, :], (b, seq))
    seen = jnp.arange(seq)[None, None, :] \
        <= attend_reach(config, positions)[:, :, None]  # [B, C, S]
    # a "window" layer's mask: fewer than attention_window rows back
    near = seen if config.attention_window is None else seen & (
        positions[:, :, None] - jnp.arange(seq)[None, None, :]
        < config.attention_window)
    group = config.n_heads // config.kv_heads

    def attend(_, attn, y, operator):
        q, k, v = gqa_qkv(attn, y, positions, config, operator != "global")
        qg = q.reshape(b, config.kv_heads, group, seq, config.head_dim)
        scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k).astype(
            jnp.float32) * config.head_dim ** -0.5
        mask = near if operator == "window" else seen
        scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum("bhgqk,bhkd->bhgqd", probs, v).reshape(q.shape)

    def conv(_, weights, y):
        zeros = jnp.zeros((b, config.conv_taps - 1, config.d_model), dtype)
        return short_conv(weights, y, zeros, dtype)[0]

    x = params["embed"][tokens].astype(dtype)
    x, _ = gqa_moe_layers(params, x, config, attend, conv_row=conv)
    x = _rms_norm(x, params["final_norm"]["scale"], config.norm_eps)
    if not apply_head:
        return x, jnp.float32(0.0)
    return ((x @ params["lm_head"].astype(dtype)).astype(jnp.float32),
            jnp.float32(0.0))


# ---------------------------------------------------------------------------
# the 'retention' block: 'gqa_moe''s projections (from weights held as
# matrices), a log gate a KV head, the
# power-retention weights of ops/retention.py in place of a softmax, and a
# dense gated FFN.  Shared by the unpaged forward below (every row against
# every earlier row) and the paged step programs (a state beside a tail).
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def retention_qkv(attn, y, positions, config: TransformerConfig,
                  rotate: bool = True):
    """A 'retention' layer's projections of ``y`` [B, C, d] at
    ``positions`` [B, C], as :func:`gqa_qkv` gives them — ``q`` [B, H, C,
    hd], ``k`` and ``v`` [B, H_kv, C, hd], q and k normed a head (unless the
    model has no such norm) and then rotated (split halves; unless the
    layer rotates nothing) — from weights held as matrices."""
    dtype, eps = config.dtype, config.norm_eps
    b, c, _ = y.shape

    def heads(w):
        out = y @ w.astype(dtype)
        return out.reshape(b, c, -1, config.head_dim).transpose(0, 2, 1, 3)

    q, k, v = heads(attn["wq"]), heads(attn["wk"]), heads(attn["wv"])
    if config.qk_norm:
        with jax.named_scope("qk_norm"):
            q = _rms_norm(q, attn["q_norm"]["scale"], eps)
            k = _rms_norm(k, attn["k_norm"]["scale"], eps)
    if not rotate:
        return q, k, v
    return (apply_rope(q, positions, theta=config.rope_theta),
            apply_rope(k, positions, theta=config.rope_theta), v)


@jax.named_scope("gate")
def retention_gate(attn, y):
    """The log gate of ``y`` [B, C, d]: ``logsigmoid(y W_g + b_g)``, float32
    [B, C, h_kv], never positive — what a row keeps of everything before
    it, one a KV head (its query heads share it)."""
    logits = jnp.einsum("bcd,dh->bch", y, attn["gate"]["w"].astype(y.dtype),
                        preferred_element_type=jnp.float32)
    return jax.nn.log_sigmoid(logits
                              + attn["gate"]["b"].astype(jnp.float32))


def retention_layers(params, x, config: TransformerConfig, attend_row):
    """Every layer of a 'retention' block over ``x`` [B, C, d]:
    ``attend_row(layer, attn_weights, y)`` is the retention's output of the
    normed input ``y``, [B, H, C, hd] before the output projection — where
    the callers differ, as in :func:`gqa_moe_layers`."""
    dtype, eps = config.dtype, config.norm_eps
    for i, layer in enumerate(params["layers"]):
        y = _rms_norm(x, layer["norm1"]["scale"], eps)
        o = attend_row(i, layer["attn"], y).astype(dtype)
        with jax.named_scope("attention"):
            x = x + jnp.einsum("bhsk,hkd->bsd", o,
                               layer["attn"]["wo"].astype(dtype))
        y = _rms_norm(x, layer["norm2"]["scale"], eps)
        with jax.named_scope("ffn"):
            x = x + gated_ffn(layer["ffn"], y, dtype)
    return x


def _retention_forward(params, tokens, config: TransformerConfig,
                       apply_head: bool = True):
    """The unpaged forward of a 'retention' block, in the quadratic form:
    every row against every earlier row, no state."""
    from ..ops.retention import retention_quadratic

    dtype = config.dtype
    b, seq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(seq)[None, :], (b, seq))

    def attend(_, attn, y):
        q, k, v = retention_qkv(attn, y, positions, config)
        return retention_quadratic(q, k, v, retention_gate(attn, y), dtype)

    x = params["embed"][tokens].astype(dtype)
    x = retention_layers(params, x, config, attend)
    x = _rms_norm(x, params["final_norm"]["scale"], config.norm_eps)
    if not apply_head:
        return x, jnp.float32(0.0)
    return ((x @ params["lm_head"].astype(dtype)).astype(jnp.float32),
            jnp.float32(0.0))


_PAGED_ONLY_FORWARD = {"latent_shortcut": _latent_forward,
                       "latent_moe": _latent_forward,
                       "gqa_moe": _gqa_moe_forward,
                       "retention": _retention_forward}


def _select_attention(config: TransformerConfig):
    kind = config.attention
    window = config.attention_window
    if kind == "auto":
        kind = "flash" if jax.devices()[0].platform == "tpu" else "reference"
    if kind == "flash":
        return lambda q, k, v: flash_attention(q, k, v, causal=True,
                                               window=window)
    if kind != "reference":  # ring/ulysses callers are routed before here
        raise ValueError(f"unknown attention kind {kind!r}")
    return lambda q, k, v: attention_reference(q, k, v, causal=True,
                                               window=window)


def _forward(params, tokens, config, attention_fn, pos_offset,
             apply_head: bool = True, kv_sink=None):
    """Shared forward body.  ``pos_offset`` supports sequence-sharded
    callers: a scalar offset for contiguous shards, or a [seq] array of
    global token positions for permuted layouts (the zigzag ring).
    ``apply_head=False`` returns the final-normed hidden states instead
    of logits (permuted-layout callers un-permute at hidden width and
    project outside — the logits would be vocab/d_model times wider).
    ``kv_sink`` (a list) collects each layer's (k, v) projections —
    the bulk-prefill path fills the decode cache from them; remat is
    bypassed there (inference has no backward to rematerialize for)."""
    if config.block in _PAGED_ONLY_FORWARD:
        if kv_sink is not None or jnp.ndim(pos_offset) != 0:
            raise ValueError(
                f"block {config.block!r} has no dense-cache or "
                f"sequence-sharded forward: its cache is the paged "
                f"pool (serving/paged.py)")
        return _PAGED_ONLY_FORWARD[config.block](params, tokens, config,
                                                 apply_head)
    dtype = config.dtype
    seq = tokens.shape[1]
    x = params["embed"][tokens].astype(dtype)
    if config.positional not in ("learned", "rope"):
        raise ValueError(
            f"positional must be 'learned' or 'rope', got {config.positional!r}"
        )
    use_rope = config.positional == "rope"
    explicit_positions = jnp.ndim(pos_offset) == 1
    if use_rope:
        positions = (pos_offset if explicit_positions
                     else rope_positions(seq, pos_offset))
    elif explicit_positions:
        x = x + params["pos_embed"][pos_offset].astype(dtype)
    else:
        pos = jax.lax.dynamic_slice_in_dim(params["pos_embed"], pos_offset, seq)
        x = x + pos.astype(dtype)

    layer_fn = _layer_forward
    if config.remat and kv_sink is None:
        # rematerialize each layer's activations in the backward pass —
        # the standard HBM-for-FLOPs trade for long sequences / deep stacks
        layer_fn = jax.checkpoint(
            _layer_forward, static_argnums=(2, 3, 5, 6, 7, 8, 9, 10)
        )
    # prefill (kv_sink set) pins the expert buffers to the token count:
    # no choice ever drops, so routing is position- and batch-independent,
    # exactly matching the incremental decode path's capacity contract
    moe_capacity = (
        tokens.shape[0] * tokens.shape[1] if kv_sink is not None else None
    )
    aux_total = jnp.float32(0.0)
    for layer in params["layers"]:
        out = layer_fn(layer, x, attention_fn, dtype,
                       positions if use_rope else None,
                       config.moe_capacity_factor, config.moe_top_k,
                       config.moe_routing, config.moe_dispatch,
                       kv_sink is not None, moe_capacity)
        if kv_sink is None:
            x, aux = out
        else:
            x, aux, kv = out
            kv_sink.append(kv)
        aux_total = aux_total + aux

    x = _rms_norm(x, params["final_norm"]["scale"])
    if not apply_head:
        return x, aux_total
    return (x @ params["lm_head"].astype(dtype)).astype(jnp.float32), aux_total


def _layer_forward(layer, x, attention_fn, dtype, rope_positions_or_none,
                   moe_capacity_factor: float = 1.25, moe_top_k: int = 1,
                   moe_routing: str = "tokens_choose",
                   moe_dispatch: str = "scatter", kv_out: bool = False,
                   moe_capacity=None):
    """One transformer layer; returns (x, aux) where aux is the MoE
    load-balancing loss (0.0 for dense-MLP layers).  ``kv_out=True``
    additionally returns the (roped) k/v projections — the bulk-prefill
    path writes them straight into the decode cache.  ``moe_capacity``
    overrides the factor-derived expert buffer (prefill pins it to the
    token count so no choice ever drops — decode's batch-independence
    contract)."""
    # attention block
    y = _rms_norm(x, layer["norm1"]["scale"])
    q = jnp.einsum("bsd,dhk->bhsk", y, layer["attn"]["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bhsk", y, layer["attn"]["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bhsk", y, layer["attn"]["wv"].astype(dtype))
    if rope_positions_or_none is not None:
        q = apply_rope(q, rope_positions_or_none)
        k = apply_rope(k, rope_positions_or_none)
    o = attention_fn(q, k, v).astype(dtype)
    x = x + jnp.einsum("bhsk,hkd->bsd", o, layer["attn"]["wo"].astype(dtype))
    # mlp / moe block
    y = _rms_norm(x, layer["norm2"]["scale"])
    if "moe" in layer:
        from ..ops.moe import MoEConfig, moe_apply

        e, d, f = layer["moe"]["w_in"].shape
        out, aux = moe_apply(
            layer["moe"], y,
            MoEConfig(d_model=d, d_ff=f, num_experts=e,
                      capacity_factor=moe_capacity_factor,
                      top_k=moe_top_k, routing=moe_routing,
                      dispatch=moe_dispatch),
            capacity=moe_capacity,
        )
        x = x + out.astype(dtype)
    else:
        y = jax.nn.gelu(y @ layer["mlp"]["w_in"].astype(dtype))
        x = x + y @ layer["mlp"]["w_out"].astype(dtype)
        aux = jnp.float32(0.0)
    if kv_out:
        return x, aux, (k, v)
    return x, aux


def transformer_apply(
    params: Dict,
    tokens: jax.Array,
    config: TransformerConfig,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """tokens: [batch, seq] int32 -> logits [batch, seq, vocab].

    ``attention="ring"`` needs a sequence-sharded caller — use
    ``transformer_apply_ring`` (this entry point has no mesh axis bound).
    """
    if config.attention in ("ring", "ulysses"):
        raise ValueError(
            f"attention={config.attention!r} shards the sequence axis; call "
            f"transformer_apply_{config.attention}(params, tokens, config, "
            f"mesh) instead"
        )
    logits, _ = _forward(params, tokens, config, _select_attention(config), 0)
    return logits


def transformer_apply_with_aux(
    params: Dict,
    tokens: jax.Array,
    config: TransformerConfig,
):
    """Like :func:`transformer_apply` but also returns the summed MoE
    load-balancing auxiliary loss (0.0 for all-dense configs) — add it to
    the training loss with a small coefficient (conventionally 1e-2)."""
    if config.attention in ("ring", "ulysses"):
        raise ValueError(
            f"attention={config.attention!r} shards the sequence axis")
    return _forward(params, tokens, config, _select_attention(config), 0)


def _validate_sp_entry(
    strategy: str, config: TransformerConfig, mesh: Mesh, seq_axis: str,
) -> None:
    """Shared preconditions for every sequence-parallel entry point (the
    standalone ring/ulysses forwards and the pipelined sp path; the
    pipelined caller adds its own MoE rejection — no aux plumbing)."""
    if seq_axis not in mesh.shape:
        raise ValueError(
            f"sequence-parallel attention needs a {seq_axis!r} mesh axis "
            f"(got {tuple(mesh.shape)})"
        )
    # a window on the CONTIGUOUS einsum ring is supported (out-of-band
    # ring steps skip their block math); the zigzag/flash ring callers
    # get a loud error at the op layer
    if strategy == "ulysses" and (
        config.n_heads % mesh.shape[seq_axis] != 0
        or config.kv_heads % mesh.shape[seq_axis] != 0
    ):
        raise ValueError(
            f"attention='ulysses' needs n_heads ({config.n_heads}) and "
            f"n_kv_heads ({config.kv_heads}) divisible by the "
            f"{seq_axis!r} mesh degree ({mesh.shape[seq_axis]})"
        )
    if (config.moe_every is not None
            and config.moe_routing == "experts_choose"):
        raise ValueError(
            "expert-choice routing is whole-batch routing (an expert picks "
            "its top-capacity tokens globally, ops/moe.py) — a sequence "
            "shard cannot route it locally; use moe_routing="
            "'tokens_choose' on the sequence-parallel entries"
        )


def _mesh_mean_aux(aux, batch_axis, seq_axis):
    """Average a per-shard MoE aux loss over the mesh axes the entry
    shards on, so the returned scalar is replicated."""
    aux = jax.lax.pmean(aux, seq_axis)
    if batch_axis is not None:
        aux = jax.lax.pmean(aux, batch_axis)
    return aux


def transformer_apply_ring(
    params: Dict,
    tokens: jax.Array,
    config: TransformerConfig,
    mesh: Mesh,
    batch_axis: Optional[str] = "dp",
    seq_axis: str = "sp",
    use_flash: Optional[bool] = None,
    interpret: bool = False,
    layout: str = "contiguous",
    with_aux: bool = False,
) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Sequence-parallel forward: tokens sharded over ``seq_axis``, ring
    attention carrying K/V around the ICI ring (long-context path).

    MoE configs route each sequence shard's tokens locally (routing is
    per-token; expert buffers derive from the shard's token count).
    ``with_aux=True`` additionally returns the load-balancing aux loss,
    averaged over the mesh — a per-shard-mean estimator of the dense
    entry's global-mean aux (biased by the per-shard covariance of the
    aux's two mean factors: a usable load-balancing signal, not exact
    loss parity with the dense entry).

    ``use_flash=None`` auto-selects the Pallas-fused ring body on TPU when
    the per-device sequence shard reaches the kernel threshold (the kernel
    win then compounds with sp — exactly where sequences are longest).

    ``layout="zigzag"`` runs the load-balanced causal ring end to end:
    tokens are permuted into zigzag order once, every layer attends with
    the balanced per-step partials (RoPE/learned positions follow the
    permuted global positions), and the logits are permuted back —
    callers see contiguous sequences."""
    from ..ops.ring_attention import (
        ring_attention_zigzag,
        ring_flash_attention_zigzag,
        zigzag_positions,
        zigzag_shard,
        zigzag_unshard,
    )

    _validate_sp_entry("ring", config, mesh, seq_axis)
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring layout {layout!r}")
    zigzag = layout == "zigzag"
    window = config.attention_window
    if window is not None:
        from ..ops.ring_attention import resolve_windowed_ring

        use_flash = resolve_windowed_ring(window, zigzag=zigzag,
                                          use_flash=use_flash)
    sp = mesh.shape[seq_axis]
    if use_flash is None:
        from ..ops.ring_attention import ring_flash_auto

        auto_len = tokens.shape[1] // 2 if zigzag else tokens.shape[1]
        use_flash = ring_flash_auto(auto_len, mesh, seq_axis, interpret)
    if zigzag:
        tokens = zigzag_shard(tokens, sp, axis=1)

    def local_forward(params, tokens):
        local_seq = tokens.shape[1]
        if zigzag:
            pos = zigzag_positions(seq_axis, local_seq)
            if use_flash:
                attention_fn = lambda q, k, v: ring_flash_attention_zigzag(
                    q, k, v, axis_name=seq_axis, interpret=interpret
                )
            else:
                attention_fn = lambda q, k, v: ring_attention_zigzag(
                    q, k, v, axis_name=seq_axis, causal=True
                )
        else:
            pos = jax.lax.axis_index(seq_axis) * local_seq
            if use_flash:
                attention_fn = lambda q, k, v: ring_flash_attention(
                    q, k, v, axis_name=seq_axis, causal=True,
                    interpret=interpret
                )
            else:
                attention_fn = lambda q, k, v: ring_attention(
                    q, k, v, axis_name=seq_axis, causal=True, window=window
                )
        # zigzag: return hidden states and project outside — the inverse
        # permutation then moves d_model-wide rows, not vocab-wide logits
        out, aux = _forward(params, tokens, config, attention_fn, pos,
                            apply_head=not zigzag)
        return out, _mesh_mean_aux(aux, batch_axis, seq_axis)

    out, aux = jax.shard_map(
        local_forward,
        mesh=mesh,
        in_specs=(P(), P(batch_axis, seq_axis)),
        out_specs=(P(batch_axis, seq_axis, None), P()),
        # only interpret-mode pallas evaluation trips the vma checker (its
        # block slicing mixes varying/invariant operands); the compiled TPU
        # kernel path keeps full checking over the whole forward
        check_vma=not (use_flash and interpret),
    )(params, tokens)
    if zigzag:
        hidden = zigzag_unshard(out, sp, axis=1)
        out = (hidden @ params["lm_head"].astype(config.dtype)).astype(
            jnp.float32)
    return (out, aux) if with_aux else out


def transformer_apply_ulysses(
    params: Dict,
    tokens: jax.Array,
    config: TransformerConfig,
    mesh: Mesh,
    batch_axis: Optional[str] = "dp",
    seq_axis: str = "sp",
    use_flash: Optional[bool] = None,
    interpret: bool = False,
    with_aux: bool = False,
) -> Union[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Sequence-parallel forward via all-to-all (Ulysses-style) attention:
    tokens sharded over ``seq_axis``; two ``all_to_all`` collectives swap
    the shards to head-parallel for a FULL-sequence local attention (the
    flash kernel at its best shapes), then swap back (ops/ulysses.py).

    Supports ``attention_window`` (the all-to-all hands each device whole
    heads over the whole sequence, so the flash kernel's banding applies
    directly; the ring composes with windows too, via its einsum body);
    needs ``n_heads % mesh.shape[seq_axis] == 0``.  MoE and ``with_aux``
    behave as on :func:`transformer_apply_ring`."""
    from ..ops.ulysses import ulysses_attention

    _validate_sp_entry("ulysses", config, mesh, seq_axis)

    def local_forward(params, tokens):
        local_seq = tokens.shape[1]
        offset = jax.lax.axis_index(seq_axis) * local_seq
        attention_fn = lambda q, k, v: ulysses_attention(
            q, k, v, axis_name=seq_axis, causal=True,
            window=config.attention_window, use_flash=use_flash,
            interpret=interpret,
        )
        logits, aux = _forward(params, tokens, config, attention_fn, offset)
        return logits, _mesh_mean_aux(aux, batch_axis, seq_axis)

    force_flash = use_flash if use_flash is not None else interpret
    out, aux = jax.shard_map(
        local_forward,
        mesh=mesh,
        in_specs=(P(), P(batch_axis, seq_axis)),
        out_specs=(P(batch_axis, seq_axis, None), P()),
        check_vma=not (force_flash and interpret),
    )(params, tokens)
    return (out, aux) if with_aux else out


def transformer_sharding_rules() -> Dict[str, P]:
    """Path-substring -> PartitionSpec rules over the (dp, tp, sp) mesh.

    tp splits attention heads and MLP hidden; embeddings/lm_head split on the
    vocab axis; norms replicate.  Used with parallel.mesh.shard_params /
    param_spec_tree.
    """
    return {
        "embed": P("tp", None),
        "pos_embed": P(),
        "wq": P(None, "tp", None),
        "wk": P(None, "tp", None),
        "wv": P(None, "tp", None),
        "wo": P("tp", None, None),
        "w_in": P(None, "tp"),
        "w_out": P("tp", None),
        # MoE layers: experts sharded over tp (ep-over-tp), router
        # replicated.  Needles are keystr substrings; the longer
        # moe-qualified patterns beat the dense "w_in"/"w_out" ones.
        "moe']['w_in": P("tp", None, None),
        "moe']['w_out": P("tp", None, None),
        "router": P(),
        "lm_head": P(None, "tp"),
        "norm": P(),
        "scale": P(),
    }


def transformer_fsdp_rules(axis: str = "dp") -> Dict[str, P]:
    """Zero-style (FSDP) parameter sharding composed WITH tensor
    parallelism: every weight matrix additionally shards a non-tp axis
    over ``axis`` (conventionally dp), so parameter and optimizer-state
    memory scale down with the dp degree.  XLA inserts the all-gathers
    at use and reduce-scatters in the backward — the GSPMD formulation
    of ZeRO-3; there is no wrapper class to write, only placement.

    Optimizer state inherits the sharding automatically: optax init
    builds moments with zeros_like over the placed params.
    """
    return {
        "embed": P("tp", axis),
        "pos_embed": P(),
        "wq": P(axis, "tp", None),
        "wk": P(axis, "tp", None),
        "wv": P(axis, "tp", None),
        "wo": P("tp", None, axis),
        "w_in": P(axis, "tp"),
        "w_out": P("tp", axis),
        # MoE experts: expert axis over tp (as in the base rules), the
        # feature axis over dp
        "moe']['w_in": P("tp", axis, None),
        "moe']['w_out": P("tp", axis, None),
        "router": P(),
        "lm_head": P(axis, "tp"),
        "norm": P(),
        "scale": P(),
    }


def transformer_activation_spec(use_sp: bool = True) -> P:
    """Sharding for the [batch, seq] token array."""
    return P("dp", "sp") if use_sp else P("dp", None)


def transformer_apply_pipelined(
    params: Dict,
    tokens: jax.Array,
    config: TransformerConfig,
    mesh: Mesh,
    num_microbatches: int = 2,
    pp_axis: str = "pp",
    seq_axis: str = "sp",
    use_flash: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """Pipeline-parallel forward: layers split into pp stages (GPipe over
    ``pp_axis``, parallel.pipeline); embedding and head run replicated
    outside the pipeline.  Requires n_layers % pp == 0.

    **pp x sp composition**: with ``attention="ring"`` or ``"ulysses"``
    (and ``seq_axis`` in the mesh), activations flow through the pipeline
    sequence-sharded — each stage runs its sequence-parallel attention
    over ``seq_axis`` internally while microbatches hop stages over
    ``pp_axis``.  The long-context strategies compose with pipeline depth
    instead of competing with it.  ``use_flash=None`` auto-selects the
    Pallas-fused bodies exactly like the standalone sp entry points
    (ring_flash_auto / the kernel threshold at full sequence)."""
    from ..parallel.pipeline import pipeline_apply

    stacked, stage_fn, activation_spec, stage_check_vma = (
        _pipeline_stage_setup(params, tokens.shape[1], config, mesh,
                              pp_axis, seq_axis, use_flash, interpret))
    dtype = config.dtype
    x = params["embed"][tokens].astype(dtype)
    if config.positional != "rope":
        x = x + params["pos_embed"][: tokens.shape[1]].astype(dtype)

    x = pipeline_apply(stacked, x, stage_fn, mesh, num_microbatches, pp_axis,
                       activation_spec=activation_spec,
                       check_vma=stage_check_vma)
    x = _rms_norm(x, params["final_norm"]["scale"])
    return (x @ params["lm_head"].astype(dtype)).astype(jnp.float32)


def _pipeline_stage_setup(params, seq_len, config, mesh, pp_axis, seq_axis,
                          use_flash, interpret):
    """Shared pipeline construction: stack layers into pp stages and build
    the stage body (with ring/Ulysses attention inside the stage when the
    config asks for sequence parallelism).  Returns
    ``(stacked_params, stage_fn, activation_spec, check_vma)``."""
    from ..parallel.pipeline import stack_stage_params

    sp_attention = config.attention in ("ring", "ulysses")
    if sp_attention:
        _validate_sp_entry(config.attention, config, mesh, seq_axis)
    if config.moe_every is not None:
        # applies to the sp branch too: the stage body would silently run
        # MoE layers with default routing hyperparameters and drop the
        # aux loss
        raise ValueError(
            "MoE layers are not supported on the pipelined path yet")
    n_stages = mesh.shape[pp_axis]
    if config.n_layers % n_stages != 0:
        raise ValueError(
            f"n_layers {config.n_layers} not divisible into {n_stages} stages"
        )
    per_stage = config.n_layers // n_stages
    dtype = config.dtype
    use_rope = config.positional == "rope"

    # stack each stage's layers: leaves [pp, per_stage, ...]
    stages = [
        jax.tree.map(lambda *ls: jnp.stack(ls),
                     *params["layers"][s * per_stage:(s + 1) * per_stage])
        for s in range(n_stages)
    ]
    stacked = stack_stage_params(stages)

    if sp_attention:
        from ..ops.ring_attention import ring_flash_auto
        from ..ops.ulysses import ulysses_attention

        ring_use_flash = use_flash
        if config.attention == "ring":
            if config.attention_window is not None:
                from ..ops.ring_attention import resolve_windowed_ring

                ring_use_flash = resolve_windowed_ring(
                    config.attention_window, use_flash=ring_use_flash)
            elif ring_use_flash is None:
                ring_use_flash = ring_flash_auto(seq_len, mesh, seq_axis,
                                                 interpret)

        def stage_fn(stage_layers, x):
            # inside shard_map over (pp, sp): x is the local sequence shard
            local_seq = x.shape[1]
            offset = jax.lax.axis_index(seq_axis) * local_seq
            pos = rope_positions(local_seq, offset) if use_rope else None
            if config.attention == "ring":
                fn = ring_flash_attention if ring_use_flash else ring_attention
                kwargs = ({"interpret": interpret} if ring_use_flash
                          else {"window": config.attention_window})
                attn = lambda q, k, v: fn(
                    q, k, v, axis_name=seq_axis, causal=True, **kwargs)
            else:
                attn = lambda q, k, v: ulysses_attention(
                    q, k, v, axis_name=seq_axis, causal=True,
                    window=config.attention_window, use_flash=use_flash,
                    interpret=interpret)

            def body(x, layer):
                x, _ = _layer_forward(layer, x, attn, dtype, pos)
                return x, None

            x, _ = jax.lax.scan(body, x, stage_layers)
            return x

        activation_spec = P(None, seq_axis, None)
        force_flash = (ring_use_flash if config.attention == "ring"
                       else (use_flash if use_flash is not None else interpret))
        stage_check_vma = not (force_flash and interpret)
    else:
        positions = rope_positions(seq_len, 0) if use_rope else None
        attention_fn = _select_attention(config)

        def stage_fn(stage_layers, x):
            def body(x, layer):
                x, _ = _layer_forward(layer, x, attention_fn, dtype,
                                      positions)
                return x, None

            x, _ = jax.lax.scan(body, x, stage_layers)
            return x

        activation_spec = None
        stage_check_vma = True

    return stacked, stage_fn, activation_spec, stage_check_vma


def transformer_train_1f1b(
    params: Dict,
    tokens: jax.Array,
    targets: jax.Array,
    config: TransformerConfig,
    mesh: Mesh,
    num_microbatches: int = 2,
    pp_axis: str = "pp",
    seq_axis: str = "sp",
    use_flash: Optional[bool] = None,
    interpret: bool = False,
):
    """Full flagship training step under the 1F1B pipeline schedule:
    cross-entropy loss and gradients for EVERY parameter — embedding and
    positional table (backpropped from the pipeline's input cotangents),
    per-stage layer stacks (1F1B proper), and final norm + lm_head
    (trained at the last stage via the pipeline's loss-param path).

    Composes with sequence parallelism exactly like
    :func:`transformer_apply_pipelined`: ``attention="ring"``/``"ulysses"``
    runs the sp collectives inside each stage while microbatches hop
    stages (1F1B x sp, the flagship schedule).  Returns ``(loss, grads)``
    with ``grads`` matching the ``params`` pytree.
    """
    from ..parallel.pipeline import pipeline_train_1f1b

    stacked, stage_fn, activation_spec, stage_check_vma = (
        _pipeline_stage_setup(params, tokens.shape[1], config, mesh,
                              pp_axis, seq_axis, use_flash, interpret))
    dtype = config.dtype
    use_rope = config.positional == "rope"
    seq = tokens.shape[1]

    x = params["embed"][tokens].astype(dtype)
    if not use_rope:
        x = x + params["pos_embed"][:seq].astype(dtype)

    loss_params = {"final_norm": params["final_norm"],
                   "lm_head": params["lm_head"]}

    from ..parallel.train import cross_entropy_loss

    def loss_fn(lp, out, y):
        z = _rms_norm(out.astype(dtype), lp["final_norm"]["scale"])
        logits = (z @ lp["lm_head"].astype(dtype)).astype(jnp.float32)
        return cross_entropy_loss(logits, y)

    loss, stage_grads, head_grads, dx = pipeline_train_1f1b(
        stacked, x, targets, stage_fn, loss_fn, mesh, num_microbatches,
        pp_axis=pp_axis, activation_spec=activation_spec,
        check_vma=stage_check_vma, loss_params=loss_params,
        return_input_grads=True,
    )

    # backprop the embedding lookup from the pipeline's input cotangents:
    # d(embed) is a scatter-add of dx over the token ids, d(pos_embed) the
    # batch-sum at each position
    dx32 = dx.astype(jnp.float32)
    grads: Dict = {
        "embed": jnp.zeros(params["embed"].shape, jnp.float32)
        .at[tokens].add(dx32).astype(params["embed"].dtype),
        "final_norm": head_grads["final_norm"],
        "lm_head": head_grads["lm_head"],
    }
    if not use_rope:
        dpos = dx32.sum(axis=0)
        grads["pos_embed"] = (
            jnp.zeros(params["pos_embed"].shape, jnp.float32)
            .at[:seq].set(dpos).astype(params["pos_embed"].dtype)
        )
    # unstack [pp, per_stage, ...] grads back into the per-layer list
    n_stages = mesh.shape[pp_axis]
    per_stage = config.n_layers // n_stages
    grads["layers"] = [
        jax.tree.map(lambda g, s=s, l=l: g[s, l], stage_grads)
        for s in range(n_stages)
        for l in range(per_stage)
    ]
    return loss, grads
