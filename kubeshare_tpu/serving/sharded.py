"""Tensor-parallel twins of the paged serving dispatches.

The serving engine (engine.py) executes a single-device model; this
module is the gate to models that don't fit one chip.  A
:class:`ShardedServingContext` stands up a serving mesh (``tp`` axis,
``parallel/mesh.py``'s :class:`MeshSpec` reused), shards the transformer
params Megatron-style (column splits for the QKV projections / MLP
``w_in`` / ``lm_head``, row splits for ``wo`` / ``w_out``), head-shards
the paged KV pool over its KV-head axis, and wraps every paged entry
point (``paged.py``) in ONE ``shard_map`` program per plan kind — the
collectives run INSIDE the compiled step, so the dispatch count the
engine already amortizes (spans, fused mixed steps) does not grow with
the device count.  PyGraph's lesson carries over: the sharded step
stays one launch per plan kind, or the host-side step loop the 1-core
captures show as the bottleneck gets worse, not better.

BIT-EXACTNESS INVARIANT — collectives move data; no collective ever
carries a partial sum.  The textbook Megatron construction psums the
row-parallel partial products (``wo``, ``w_out``), which changes the
floating-point reduction order and drifts streams by ~1e-6 per layer —
unacceptable here, where every engine property (prefix cache,
preemption-resume, speculation, disagg migration) is locked by
bit-exact stream comparisons.  Instead:

- column-parallel compute is genuinely sharded: QKV projections,
  per-head attention over the local KV-head shard, the MLP's
  ``w_in``/gelu half, and the lm_head's vocab columns — einsums whose
  contraction axis is UNSHARDED, so a weight-column subset yields an
  exact slice of the full result;
- before every contraction over a previously-sharded axis, the
  activations AND the row-sharded weight are ``all_gather``-ed
  (pure data movement), and the contraction runs in single-device
  operation order on every device, redundantly but exactly.

Streams from a sharded engine are therefore BIT-IDENTICAL to the
single-device engine — greedy and sampled, GQA/windowed/MoE, on a
forced multi-device CPU mesh (``--xla_force_host_platform_device_count``)
exactly as on real chips; tests/test_sharded_serving.py locks it.

Sharding decision (:func:`plan_sharding`), per config x tp:

- ``kv_heads % tp == 0`` (and >= tp): attention head-sharded — each
  device owns ``kv_heads/tp`` KV heads and their GQA query-head groups,
  and the pool's KV-head axis is sharded so a head group's cache rows
  live on their owning device;
- ``kv_heads < tp`` (e.g. MQA on a 4-way mesh): attention falls back
  to REPLICATED KV — splitting query heads across devices would break
  the GQA grouping (a device with fewer query heads than KV heads
  cannot form its groups), so attention computes redundantly on every
  device while the MLP halves stay sharded.  Test-locked bit-exact;
- ``kv_heads >= tp`` but not divisible: loud :class:`ValueError` — a
  silently unbalanced head split is a debugging trap;
- MoE expert weights stay replicated: expert-parallel dispatch psums
  partial outputs, which breaks the no-partial-sums invariant
  (expert sharding under serving is an open follow-up — ROADMAP.md).

LONG-CONTEXT ROUTING (``long_context_threshold``): a prefill chunk at
or past the threshold re-shards Ulysses-style inside the program — an
``all_to_all`` swaps the head shard for a sequence shard (all heads,
``C/tp`` query rows per device), the KV view is gathered, and each
device attends its query rows only, turning the attention's query-time
compute from head-parallel to sequence-parallel (the better split when
C is large and heads are few).  Every step is data movement or
per-query-row-independent math, so the route is bit-exact with the
head-sharded path and the single-device engine — same ``ops/ulysses.py``
construction, applied to the paged chunk.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.decoding import _attend_cached, speculative_acceptance
from ..models.transformer import TransformerConfig, _rms_norm
from ..ops.rope import apply_rope
from ..parallel.mesh import MeshSpec, make_mesh, param_spec_tree, shard_params
from .paged import (_attend_view, _decode_loop_impl, _layer_views,
                    _moe_or_mlp, _spec_loop_impl, _write_rows,
                    paged_copy_block, paged_upload_block)

# the paged pool is [n_layers, num_blocks, kv_heads, block_size, head_dim];
# head-sharding splits axis 2, so every block's rows for a device's KV
# heads are device-local (writes and gathers never cross devices)
KV_POOL_SPEC = P(None, None, "tp", None, None)


from dataclasses import dataclass


@dataclass(frozen=True)
class ShardDecision:
    """How one (config, tp) pair shards — the module docstring's policy
    made explicit, so tests and the example can print it."""

    tp: int
    attn_sharded: bool   # heads + KV pool split; False = replicated-KV
    mlp_sharded: bool    # dense mlp w_in/w_out split (MoE always repl.)
    lm_head_sharded: bool  # vocab columns split


def plan_sharding(config: TransformerConfig, tp: int) -> ShardDecision:
    """Decide the sharding layout for ``config`` on a ``tp``-way mesh;
    degenerate splits fail loudly, GQA-narrow configs fall back."""
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    h_kv = config.kv_heads
    if h_kv < tp:
        # MQA/narrow-GQA fallback: fewer KV heads than devices.
        # Query-head sharding would leave a device with a fraction of
        # a GQA group, so the whole attention (and the pool) replicates.
        attn = False
    elif h_kv % tp != 0:
        raise ValueError(
            f"num_kv_heads {h_kv} is not divisible by tp={tp} — an "
            f"unbalanced KV-head split cannot be represented; use a tp "
            f"that divides the KV heads (tp > kv_heads selects the "
            f"replicated-KV fallback instead)")
    else:
        attn = True
    if attn and config.n_heads % tp != 0:
        # unreachable when n_heads % kv_heads == 0 (transformer_init
        # enforces it), but a loud guard beats a silent bad reshape
        raise ValueError(
            f"n_heads {config.n_heads} is not divisible by tp={tp}")
    if config.d_ff % tp != 0:
        raise ValueError(
            f"d_ff {config.d_ff} is not divisible by tp={tp} — the MLP "
            f"hidden split would be unbalanced")
    return ShardDecision(
        tp=tp,
        attn_sharded=attn,
        mlp_sharded=True,
        # replicated fallback: an uneven vocab split is legal to refuse
        # quietly (the lm_head is one matmul; replication only costs
        # redundant FLOPs, never correctness)
        lm_head_sharded=config.vocab_size % tp == 0,
    )


def serving_sharding_rules(decision: ShardDecision) -> Dict[str, P]:
    """Path-substring -> PartitionSpec rules for the serving mesh —
    ``transformer_sharding_rules`` narrowed to the no-partial-sums
    layout: embed and norms replicate (every device embeds the chunk),
    MoE experts replicate (see module docstring), and the row-parallel
    weights (``wo``/``w_out``) are STORED sharded but gathered inside
    the step before their contraction."""
    rules: Dict[str, P] = {}
    if decision.attn_sharded:
        rules.update({
            "wq": P(None, "tp", None),
            "wk": P(None, "tp", None),
            "wv": P(None, "tp", None),
            "wo": P("tp", None, None),
        })
    if decision.mlp_sharded:
        rules.update({
            "w_in": P(None, "tp"),
            "w_out": P("tp", None),
            # longest-needle-first matching: keep MoE expert stacks off
            # the dense mlp rules (expert psum breaks bit-exactness)
            "moe']['w_in": P(),
            "moe']['w_out": P(),
        })
    if decision.lm_head_sharded:
        rules["lm_head"] = P(None, "tp")
    return rules


# ---------------------------------------------------------------------------
# local (per-device) bodies — the math paged.py runs, on one shard
# ---------------------------------------------------------------------------

def _chunk_attend(cfg: TransformerConfig, dec: ShardDecision,
                  lct: Optional[int], q, pool_k, pool_v, layer_idx, tables,
                  positions):
    """One layer's attention for a [P, C] chunk on this device's shard.

    Head-sharded: q carries the local query-head group, the views carry
    the local KV heads — per-head attention is independent, so the
    local output is an exact slice of the full one.  Past the
    long-context threshold (prefill only), the Ulysses re-shard swaps
    heads for sequence: all_to_all q to [P, H, C/tp, d], gather the KV
    views, attend this device's query rows, and swap back — every step
    data movement or per-query-row math, so still exact.  Otherwise
    the attention is paged's own (``_attend_view``: a long view a key
    block at a time, as far as the lanes reach)."""
    c = q.shape[2]
    if (dec.attn_sharded and lct is not None and c >= lct
            and c % dec.tp == 0):
        view_k, view_v = _layer_views(pool_k, pool_v, layer_idx, tables)
        q_s = lax.all_to_all(q, "tp", split_axis=2, concat_axis=1,
                             tiled=True)
        vk = lax.all_gather(view_k, "tp", axis=1, tiled=True)
        vv = lax.all_gather(view_v, "tp", axis=1, tiled=True)
        shard = c // dec.tp
        pos_s = lax.dynamic_slice_in_dim(
            positions, lax.axis_index("tp") * shard, shard, axis=1)
        o_s = _attend_cached(
            q_s, vk, vv, pos_s, window=cfg.attention_window
        ).astype(cfg.dtype)
        return lax.all_to_all(o_s, "tp", split_axis=1, concat_axis=2,
                              tiled=True)
    return _attend_view(q, pool_k, pool_v, layer_idx, tables, positions,
                        cfg.attention_window).astype(cfg.dtype)


def _ffn(layer, cfg: TransformerConfig, dec: ShardDecision, y):
    """Post-attention feed-forward: dense MLP sharded (w_in columns
    local, hidden + row weight gathered before the second matmul), MoE
    layers replicated through paged's exact ``_moe_or_mlp``."""
    if "moe" in layer or not dec.mlp_sharded:
        return _moe_or_mlp(layer, cfg, y)
    hid = jax.nn.gelu(y @ layer["mlp"]["w_in"].astype(cfg.dtype))
    hid = lax.all_gather(hid, "tp", axis=2, tiled=True)
    w_out = lax.all_gather(
        layer["mlp"]["w_out"].astype(cfg.dtype), "tp", axis=0, tiled=True)
    return hid @ w_out


def _chunk_stack(params, cfg: TransformerConfig, dec: ShardDecision,
                 lct: Optional[int], pool_k, pool_v, tables, positions,
                 valid, tokens):
    """The full layer stack for a [P, C] chunk against each lane's
    paged view — the ONE local body behind every sharded twin.  The
    decode step is the C=1 chunk (positions [S, 1], its scatter writes
    the identical pool elements as paged_decode_step's), the verify
    span is the width-W chunk with per-column validity; prefill is the
    chunk as-is.  Returns (x after final norm, pool_k, pool_v)."""
    dtype = cfg.dtype
    bs = pool_k.shape[3]
    blk = jnp.take_along_axis(tables, positions // bs, axis=1)
    blk = jnp.where(valid, blk, 0)
    off = positions % bs
    # the clamp covers verify's -1 pad columns; real tokens are >= 0 so
    # the gathered rows are identical to the unclamped gather
    x = params["embed"][jnp.maximum(tokens, 0)].astype(dtype)
    use_rope = cfg.positional == "rope"
    if not use_rope:
        x = x + params["pos_embed"][positions].astype(dtype)

    for layer_idx, layer in enumerate(params["layers"]):
        y = _rms_norm(x, layer["norm1"]["scale"])
        # column-parallel: sharded weights project the LOCAL head group
        q = jnp.einsum("bsd,dhk->bhsk", y, layer["attn"]["wq"].astype(dtype))
        k = jnp.einsum("bsd,dhk->bhsk", y, layer["attn"]["wk"].astype(dtype))
        v = jnp.einsum("bsd,dhk->bhsk", y, layer["attn"]["wv"].astype(dtype))
        if use_rope:
            q = apply_rope(q, positions)
            k = apply_rope(k, positions)
        # local KV heads land in the local pool shard (no collective)
        pool_k, pool_v = _write_rows(
            pool_k, pool_v, layer_idx, blk, off,
            k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
        o = _chunk_attend(cfg, dec, lct, q, pool_k, pool_v, layer_idx,
                          tables, positions)
        wo = layer["attn"]["wo"].astype(dtype)
        if dec.attn_sharded:
            # gather the head-sharded activations AND the row-sharded
            # weight, then contract in single-device order — the
            # no-partial-sums rule (a psum here would drift streams)
            o = lax.all_gather(o, "tp", axis=1, tiled=True)
            wo = lax.all_gather(wo, "tp", axis=0, tiled=True)
        x = x + jnp.einsum("bhsk,hkd->bsd", o, wo)
        y = _rms_norm(x, layer["norm2"]["scale"])
        x = x + _ffn(layer, cfg, dec, y)

    return _rms_norm(x, params["final_norm"]["scale"]), pool_k, pool_v


def _project_rows(params, cfg: TransformerConfig, dec: ShardDecision, x):
    """lm_head over [P, R, d] rows: local vocab columns, gathered in
    f32 (column subsets are exact slices — contraction over unsharded
    d_model)."""
    logits = (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    if dec.lm_head_sharded:
        logits = lax.all_gather(logits, "tp", axis=2, tiled=True)
    return logits


def _local_prefill(params, cfg, dec, lct, pool_k, pool_v, tables, starts,
                   active, tokens, last_rows):
    """paged_prefill_step's per-device body."""
    chunk = tokens.shape[1]
    positions = starts[:, None] + jnp.arange(chunk)[None, :]
    x, pk, pv = _chunk_stack(params, cfg, dec, lct, pool_k, pool_v,
                             tables, positions, active[:, None], tokens)
    head_in = jnp.take_along_axis(x, last_rows[:, None, None], axis=1)
    return _project_rows(params, cfg, dec, head_in)[:, 0], pk, pv


def _local_decode_step(params, cfg, dec, pool_k, pool_v, tables, lengths,
                       active, tokens):
    """paged_decode_step as the C=1 chunk — identical element writes
    and identical per-row attention, so identical values."""
    positions = lengths[:, None]
    x, pk, pv = _chunk_stack(params, cfg, dec, None, pool_k, pool_v,
                             tables, positions, active[:, None],
                             tokens[:, None])
    return _project_rows(params, cfg, dec, x)[:, 0], pk, pv


def _local_decode_span(params, cfg, dec, pick_fn, span, eos, pool_k,
                       pool_v, tables, lengths, active, tokens, temps,
                       keys, budgets):
    """paged_decode_span's body with the sharded step — the scan (and
    the pick) run INSIDE the program, one launch per span; the gathered
    logits are replicated, so every device picks the same token."""

    def body(carry, i):
        pk, pv, lens, toks, alive = carry
        logits, pk, pv = _local_decode_step(
            params, cfg, dec, pk, pv, tables, lens, alive, toks)
        nxt = pick_fn(logits, temps, keys[:, i])
        lens = lens + alive.astype(jnp.int32)
        cont = alive & (i + 1 < budgets)
        if eos is not None:
            cont = cont & (nxt != eos)
        return (pk, pv, lens, nxt, cont), nxt

    carry = (pool_k, pool_v, lengths, tokens, active)
    (pk, pv, _, _, _), emitted = jax.lax.scan(body, carry,
                                              jnp.arange(span))
    return emitted, pk, pv


def _local_verify_span(params, cfg, dec, pick_fn, pool_k, pool_v, tables,
                       lengths, active, tokens, widths, temps, keys):
    """paged_verify_span's per-device body: width-W chunk, per-column
    picks on the gathered logits, the dense acceptance rule."""
    w = tokens.shape[1]
    positions = lengths[:, None] + jnp.arange(w)[None, :]
    valid = active[:, None] & (jnp.arange(w)[None, :] < widths[:, None])
    x, pk, pv = _chunk_stack(params, cfg, dec, None, pool_k, pool_v,
                             tables, positions, valid, tokens)
    logits = _project_rows(params, cfg, dec, x)
    picked = jnp.stack(
        [pick_fn(logits[:, i], temps, keys[:, i]) for i in range(w)],
        axis=1)
    accepts = speculative_acceptance(tokens[:, 1:], picked)
    return picked, accepts, pk, pv


# ---------------------------------------------------------------------------
# the context: mesh + placement + shard_map twins of every entry point
# ---------------------------------------------------------------------------

def carve_replica_groups(mesh_spec: MeshSpec, devices=None) -> List[list]:
    """Resolve a ``dp > 1`` serving :class:`MeshSpec` into per-replica
    tp device groups — the fleet's side of the dp axis.

    A single engine never runs dp (slots are its batch axis); instead
    the fleet (serving/fleet.py) stands up ``dp`` engines and hands
    replica ``i`` the contiguous device slice ``[i*tp, (i+1)*tp)``.
    Each group then backs either a plain engine pinned to its one
    device (tp=1) or a tensor-parallel engine whose private
    ``MeshSpec(dp=1, tp=tp)`` mesh is built over exactly that group.
    ``dp=-1`` fills: as many replicas as the device count covers.
    Pure list slicing — no mesh is built here, so validation tests run
    on any device count (including one CPU device with dp probed
    against an explicit ``devices`` list)."""
    if mesh_spec.ep != 1 or mesh_spec.sp != 1:
        raise ValueError(
            f"carve_replica_groups carves dp x tp only: mesh_spec must "
            f"have ep=sp=1, got {mesh_spec}")
    tp = mesh_spec.tp
    if tp < 1:
        raise ValueError(
            f"carve_replica_groups needs an explicit tp >= 1 (the "
            f"per-replica mesh width cannot be inferred), got tp={tp}")
    avail = list(devices) if devices is not None else list(jax.devices())
    dp = mesh_spec.dp
    if dp == -1:
        dp = len(avail) // tp
        if dp < 1:
            raise ValueError(
                f"mesh_spec {mesh_spec} fills dp from {len(avail)} "
                f"device(s) but tp={tp} does not fit even once")
    elif dp < 1:
        raise ValueError(
            f"dp must be >= 1 or -1 (fill), got dp={dp}")
    need = dp * tp
    if len(avail) < need:
        raise ValueError(
            f"mesh_spec {mesh_spec} needs {need} devices "
            f"({dp} replicas x tp={tp}), only {len(avail)} available")
    return [avail[i * tp: (i + 1) * tp] for i in range(dp)]


class ShardedServingContext:
    """Everything the engine needs to run its dispatches tensor-parallel.

    Built from :class:`EngineConfig.mesh_spec`; owns the mesh, the
    :class:`ShardDecision`, parameter placement, the pool's
    :class:`NamedSharding`, and one ``shard_map``-wrapped twin per paged
    entry point.  The engine swaps ONLY its step closures — scheduler,
    allocator, prefix trie, tiering, and migration are untouched (host
    reads of the sharded pool gather transparently; promotions and
    migration unpacks re-scatter through the sharded upload twin)."""

    def __init__(
        self,
        config: TransformerConfig,
        mesh_spec: MeshSpec,
        params,
        *,
        long_context_threshold: Optional[int] = None,
        devices=None,
    ) -> None:
        from .kv_blocks import require_kv_heads

        require_kv_heads(config, "ShardedServingContext (the pool is "
                         "sharded over its KV-head axis)")
        if mesh_spec.dp != 1 or mesh_spec.ep != 1 or mesh_spec.sp != 1:
            raise ValueError(
                f"a SINGLE engine shards tensor-parallel only: "
                f"mesh_spec must have dp=ep=sp=1 (slots are the batch "
                f"axis inside one engine), got {mesh_spec} — dp > 1 is "
                f"the replica axis: hand this spec to "
                f"serving/fleet.ReplicaFleet, which carves it into "
                f"per-replica tp device groups via "
                f"carve_replica_groups and runs one engine per group")
        if (long_context_threshold is not None
                and long_context_threshold < 1):
            raise ValueError(
                f"long_context_threshold must be >= 1 or None, got "
                f"{long_context_threshold}")
        self.config = config
        self.tp = mesh_spec.tp
        if devices is None:
            avail = jax.devices()
            if len(avail) < self.tp:
                raise ValueError(
                    f"mesh_spec tp={self.tp} needs {self.tp} devices, "
                    f"only {len(avail)} available")
            devices = avail[: self.tp]
        self.mesh: Mesh = make_mesh(mesh_spec, devices=devices)
        self.decision = plan_sharding(config, self.tp)
        self.lct = long_context_threshold
        self.rules = serving_sharding_rules(self.decision)
        self._pspecs = param_spec_tree(params, self.rules)
        self.kv_spec = (KV_POOL_SPEC if self.decision.attn_sharded
                        else P())
        self.kv_sharding = NamedSharding(self.mesh, self.kv_spec)
        self._n_moe = sum(1 for layer in params["layers"]
                          if "moe" in layer)

        cfg, dec, lct = config, self.decision, self.lct
        kv, r = self.kv_spec, P()

        def prefill_local(w, pk, pv, tables, starts, active, tokens,
                          last_rows):
            return _local_prefill(w, cfg, dec, lct, pk, pv, tables,
                                  starts, active, tokens, last_rows)

        # check_vma=False: the replicated outputs (logits, picks) are
        # produced by all_gathers, which shard_map's replication checker
        # can't prove replicated — they are, by construction
        self.prefill = self._smap(
            prefill_local,
            (self._pspecs, kv, kv, r, r, r, r, r), (r, kv, kv))

        self.copy_block = self._smap(
            paged_copy_block, (kv, kv, r, r), (kv, kv))
        # the promotion/migration slab arrives host-shaped
        # [n_layers, kv_heads, block_size, head_dim]; head-sharding its
        # in_spec re-scatters it so each device writes its head slice
        slab = (P(None, "tp", None, None) if dec.attn_sharded else P())
        self.upload_block = self._smap(
            paged_upload_block, (kv, kv, r, slab, slab), (kv, kv))

    def _smap(self, fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def place_params(self, params):
        """Device_put the param tree under the serving rules (sharded
        weights split, everything else replicated across the mesh)."""
        return shard_params(params, self.rules, self.mesh)

    def place_pool(self, pool_k, pool_v):
        """Commit existing pool buffers to the KV sharding (head axis
        split when attention is sharded, replicated otherwise)."""
        return (jax.device_put(pool_k, self.kv_sharding),
                jax.device_put(pool_v, self.kv_sharding))

    # ---- engine-facing twins (signatures mirror the paged closures) ----

    def decode_span(self, pick_fn, span: int, eos):
        cfg, dec = self.config, self.decision
        kv, r = self.kv_spec, P()

        def local(w, pk, pv, tables, lengths, active, tokens, temps,
                  keys, budgets):
            return _local_decode_span(
                w, cfg, dec, pick_fn, span, eos, pk, pv, tables, lengths,
                active, tokens, temps, keys, budgets)

        return self._smap(
            local, (self._pspecs, kv, kv, r, r, r, r, r, r, r),
            (r, kv, kv))

    def decode_loop(self, pick_fn, span: int, k_units: int, eos):
        """The device-resident multi-step loop's sharded twin: the
        while-loop AND the collectives live inside ONE shard_map
        program (``paged._decode_loop_impl`` over the local decode
        step).  The loop condition reads only replicated values (the
        gathered logits make every device's picks — and therefore its
        alive masks — identical), so all devices take the same number
        of units and the ring/units outputs are replicated by
        construction."""
        cfg, dec = self.config, self.decision
        kv, r = self.kv_spec, P()

        def local(w, pk, pv, tables, lengths, active, tokens, temps,
                  keys, budgets):
            def step_fn(spk, spv, tbl, lens, alive, toks):
                return _local_decode_step(
                    w, cfg, dec, spk, spv, tbl, lens, alive, toks)

            return _decode_loop_impl(
                step_fn, pick_fn, span, k_units, eos, pk, pv, tables,
                lengths, active, tokens, temps, keys, budgets)

        return self._smap(
            local, (self._pspecs, kv, kv, r, r, r, r, r, r, r),
            (r, r, kv, kv))

    def spec_loop(self, pick_fn, k_units: int, eos, max_order: int,
                  redraft: float, width: int):
        """Device residency v2's sharded twin: verify-in-loop plus the
        admission ring inside ONE shard_map program
        (``paged._spec_loop_impl`` over the local verify span).  Like
        ``decode_loop``, the while-loop condition reads only replicated
        values — the gathered logits make every device's per-column
        picks, acceptance counts, alive masks, re-draft flag, and ring
        head identical — so all devices take the same number of units
        and every non-pool output is replicated by construction."""
        cfg, dec = self.config, self.decision
        kv, r = self.kv_spec, P()

        def local(w, pk, pv, tables, lengths, active, tokens, temps,
                  keys, budgets, hist, hist_len, draft_caps,
                  ring_tables, ring_lengths, ring_tokens, ring_temps,
                  ring_keys, ring_budgets, ring_hist, ring_hist_len,
                  ring_caps, ring_count):
            def verify_fn(spk, spv, tbl, lens, alive, toks, widths,
                          tmp, ukeys):
                return _local_verify_span(
                    w, cfg, dec, pick_fn, spk, spv, tbl, lens, alive,
                    toks, widths, tmp, ukeys)

            return _spec_loop_impl(
                verify_fn, k_units, eos, max_order, redraft, width,
                pk, pv, tables, lengths, active, tokens, temps, keys,
                budgets, hist, hist_len, draft_caps, ring_tables,
                ring_lengths, ring_tokens, ring_temps, ring_keys,
                ring_budgets, ring_hist, ring_hist_len, ring_caps,
                ring_count)

        return self._smap(
            local, (self._pspecs, kv, kv) + (r,) * 20,
            (r, r, r, r, r, kv, kv))

    def verify_span(self, pick_fn):
        cfg, dec = self.config, self.decision
        kv, r = self.kv_spec, P()

        def local(w, pk, pv, tables, lengths, active, tokens, widths,
                  temps, keys):
            return _local_verify_span(
                w, cfg, dec, pick_fn, pk, pv, tables, lengths, active,
                tokens, widths, temps, keys)

        return self._smap(
            local, (self._pspecs, kv, kv, r, r, r, r, r, r, r),
            (r, r, kv, kv))

    def mixed_step(self, pick_fn, span: int, eos):
        """The fused prefill + decode-span twin: both phases inside ONE
        shard_map program, the same composition-over-disjoint-blocks
        argument as ``paged_mixed_step``."""
        cfg, dec, lct = self.config, self.decision, self.lct
        kv, r = self.kv_spec, P()

        def local(w, pk, pv, p_table, p_start, p_tokens, p_last_row,
                  p_temp, p_key, d_tables, d_lengths, d_active, d_tokens,
                  d_temps, d_keys, d_budgets):
            p_logits, pk, pv = _local_prefill(
                w, cfg, dec, lct, pk, pv, p_table, p_start,
                jnp.ones_like(p_start, bool), p_tokens, p_last_row)
            p_picked = pick_fn(p_logits, p_temp, p_key)
            emitted, pk, pv = _local_decode_span(
                w, cfg, dec, pick_fn, span, eos, pk, pv, d_tables,
                d_lengths, d_active, d_tokens, d_temps, d_keys,
                d_budgets)
            return p_picked, emitted, pk, pv

        return self._smap(
            local,
            (self._pspecs, kv, kv, r, r, r, r, r, r, r, r, r, r, r, r,
             r),
            (r, r, kv, kv))

    def mixed_verify_step(self, pick_fn):
        cfg, dec, lct = self.config, self.decision, self.lct
        kv, r = self.kv_spec, P()

        def local(w, pk, pv, p_table, p_start, p_tokens, p_last_row,
                  p_temp, p_key, d_tables, d_lengths, d_active, d_tokens,
                  d_widths, d_temps, d_keys):
            p_logits, pk, pv = _local_prefill(
                w, cfg, dec, lct, pk, pv, p_table, p_start,
                jnp.ones_like(p_start, bool), p_tokens, p_last_row)
            p_picked = pick_fn(p_logits, p_temp, p_key)
            picked, accepts, pk, pv = _local_verify_span(
                w, cfg, dec, pick_fn, pk, pv, d_tables, d_lengths,
                d_active, d_tokens, d_widths, d_temps, d_keys)
            return p_picked, picked, accepts, pk, pv

        return self._smap(
            local,
            (self._pspecs, kv, kv, r, r, r, r, r, r, r, r, r, r, r, r,
             r),
            (r, r, r, kv, kv))

    # ---- observability -------------------------------------------------

    def dispatch_collective_bytes(self, kind: str, *, lanes: int,
                                  chunk: int = 0, span: int = 0,
                                  width: int = 0,
                                  view_rows: int = 0) -> int:
        """ESTIMATED fleet-total bytes one dispatch of ``kind`` moves
        through its collectives, from the shard shapes (the metrics
        plane's ``collective_bytes_total`` counter — an estimate, not a
        transport measurement): an all_gather of a globally-N-byte
        tensor lands N*(tp-1) bytes across the fleet; an all_to_all
        moves N*(tp-1)/tp.  Copy/upload dispatches are collective-free
        (pure local writes) and cost 0."""
        if kind in ("prefill", "prefill_chunk"):
            return self._chunk_bytes(lanes, chunk, 1, view_rows)
        if kind in ("decode", "decode_span"):
            return span * self._chunk_bytes(lanes, 1, 1, view_rows)
        if kind in ("verify", "verify_span"):
            return self._chunk_bytes(lanes, width, width, view_rows)
        if kind in ("cow_copy", "upload"):
            return 0
        raise ValueError(f"unknown dispatch kind {kind!r}")

    def _chunk_bytes(self, lanes: int, c: int, logit_rows: int,
                     view_rows: int) -> int:
        cfg, dec = self.config, self.decision
        size = jnp.dtype(cfg.dtype).itemsize
        tp1 = self.tp - 1
        total = 0
        if dec.attn_sharded:
            o_bytes = lanes * cfg.n_heads * c * cfg.head_dim * size
            wo_bytes = cfg.n_heads * cfg.head_dim * cfg.d_model * size
            total += cfg.n_layers * (o_bytes + wo_bytes) * tp1
            if (self.lct is not None and c >= self.lct
                    and c % self.tp == 0):
                # Ulysses re-route: two all_to_alls on q/o plus the
                # gathered KV views
                q_bytes = lanes * cfg.n_heads * c * cfg.head_dim * size
                view_bytes = (lanes * cfg.kv_heads * view_rows
                              * cfg.head_dim * size)
                total += cfg.n_layers * (
                    2 * q_bytes * tp1 // self.tp + 2 * view_bytes * tp1)
        if dec.mlp_sharded:
            n_dense = cfg.n_layers - self._n_moe
            hid_bytes = lanes * c * cfg.d_ff * size
            w_out_bytes = cfg.d_ff * cfg.d_model * size
            total += n_dense * (hid_bytes + w_out_bytes) * tp1
        if dec.lm_head_sharded:
            total += lanes * logit_rows * cfg.vocab_size * 4 * tp1
        return total

    def describe(self) -> Dict[str, object]:
        """Human-facing summary (the example script prints it)."""
        dec = self.decision
        return {
            "tp": self.tp,
            "devices": [str(d) for d in self.mesh.devices.flat],
            "attn_sharded": dec.attn_sharded,
            "mlp_sharded": dec.mlp_sharded,
            "lm_head_sharded": dec.lm_head_sharded,
            "kv_pool_spec": str(self.kv_spec),
            "kv_heads_per_device": (
                self.config.kv_heads // self.tp if dec.attn_sharded
                else self.config.kv_heads),
            "long_context_threshold": self.lct,
        }
