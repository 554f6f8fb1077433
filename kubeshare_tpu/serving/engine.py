"""Continuous-batching serving engine over the paged KV pool.

The run-to-completion serving path (one fixed batch prefills, decodes to
a uniform length, then the next batch starts) wastes the chip twice:
short requests wait on the batch's longest, and every batch row reserves
``max_seq_len`` of cache whether it needs it or not.  This engine
schedules at TOKEN granularity instead:

- a static pool of S slots runs ONE jitted decode step per iteration —
  every active slot advances a token, each at its own length (the paged
  step's per-row positions);
- queued requests are admitted into freed slots MID-FLIGHT — admission
  reserves exactly the blocks the request can ever touch
  (prompt + max_new_tokens, rounded to blocks), and a reservation the
  pool cannot fund queues the request rather than clamping anything;
- prompts prefill in fixed-width chunks (widths bucketed to powers of
  two, so ragged prompts hit O(log chunk) compiled shapes, not one per
  remainder), filling slots rotating round-robin so one many-chunk
  prompt cannot monopolize prefill ticks;
- decode advances every active slot ``decode_span`` tokens per dispatch
  (a lax.scan of step-identical iterations; lanes self-deactivate on
  budget/EOS) — dispatch overhead amortized the way the PyGraph line of
  work batches GPU launches;
- STALL-FREE MIXED BATCHING (on by default): when prefill and decode
  work coexist, one fused dispatch (paged.paged_mixed_step) advances
  every decode lane by its span AND consumes one prefill chunk bounded
  by ``mixed_prefill_budget`` tokens — decode lanes never wait behind a
  long prompt (the either/or Orca discipline stalls every in-flight
  lane for every chunk, spiking inter-token latency across all
  tenants), and a fused step pays ONE launch where the split path pays
  two.  Chunks wider than the budget are sliced to already-warmed
  power-of-two pieces, so the added latency any decode lane (a
  Guarantee tenant's included) pays per admission ride-along is
  bounded by the budget — and warmup covers one mixed shape per
  existing prefill bucket, preserving the zero-recompile invariant.
  The chunk rides the span's first pass over the weights (its rows and
  the lanes' first rows through one layer loop: ``decode_span`` passes a
  dispatch, ``weight_passes`` on the launch span; a model with a state
  by slot rides it too, its state phase a group of lanes at a time as the
  attention).  Streams are token for
  token those of ``mixed=False`` (the two sides write disjoint blocks,
  and a row's math is the split entry points' — hard-asserted by the
  tests);
- host/device overlap: dispatches synchronize ONLY when charging an
  ExecutionGuard (token accounting needs measured wall time);
  unguarded, the engine pipelines one step ahead — admission and the
  caller's arrival loop run while the device executes, and emitted
  tokens are read when the next step consumes them;
- slots retire on EOS / max-tokens; their blocks drop their reference
  and the next queued request takes them over;
- a radix-tree PREFIX CACHE (prefix_index.py) makes retired prompts'
  blocks content-addressable: admission walks the new prompt down the
  trie, maps every matched block into the slot's page table (refcount
  +1 per reader — shared system prompts are stored ONCE), and starts
  prefill at the first uncached token.  A prompt diverging mid-block
  gets a copy-on-write private copy of the shared tail block before it
  appends.  Retired blocks park in an idle-cached LRU pool instead of
  freeing eagerly; eviction drains it only when a reservation would
  otherwise fail (kv_blocks.py) — so the cache uses exactly the HBM
  admission doesn't need, and the emitted streams stay bit-exact with
  the cache disabled (test-locked, like every other engine property);
- KV CACHE TIERING (kv_tier.py, ``host_tier_bytes``): eviction no
  longer destroys a prefix — the victim subtree's blocks are
  serialized (versioned wire format) into a byte-budgeted host-RAM
  tier through a pluggable TierPolicy (LRU, or QoS-aware protecting
  Guarantee-charged prefixes), the trie keeps the nodes HOST-resident,
  and a later admission that matches them PROMOTES the payloads back
  into freshly reserved device blocks via one warmed compiled upload
  shape, overlapping the copy-in with the pipelined dispatch.  The
  tenant quota ledger stays honest: demotion releases the device
  blocks (uncharging their tenant), promotion is a normal charged
  reservation.  Hit-rate, not HBM, sets the cache ceiling; streams
  stay bit-exact with tiering off.

- GENERATION BY DIFFUSION OVER BLOCKS (a model whose
  ``TransformerConfig.diffusion_block`` is B > 0): a dispatch no longer
  yields one token a lane.  Prefill covers the prompt's whole blocks
  and yields no token; a lane holds a block state (which of B positions
  are committed) and each dispatch is ONE pass over every lane's block
  — a denoising pass that commits 0..B rows by confidence, or the
  commit pass over a finished block, after which the lane's cached
  length advances by B.  The same plan / dispatch / consume machinery
  carries them (plan kinds "diffusion" and "mixed_diffusion"); a token
  counts once, when it is served (docs/serving.md).

Everything device-side is static-shaped — slot count, block tables,
chunk widths — so after one warmup pass NOTHING recompiles
(``compile_counts`` exposes the jit cache sizes; the zero-recompile
property is test-asserted; the benchmark counts a window's compiles).

Fractional-chip integration: every device dispatch (prefill chunk with
its fused first-token pick, decode span) charges through an
:class:`~kubeshare_tpu.isolation.ExecutionGuard` when one is given, so a
0.5-chip serving pod's engine is gated exactly like the run-to-
completion path it replaces (examples/serve_fractional.py).

MULTI-TENANT QoS (qos.py): requests name a TENANT; admission pulls from
a token-weighted fair queue (Guarantee class strictly ahead of
Opportunistic, decayed service/weight within a class — tokend's share
model applied to tokens) instead of global FIFO; per-tenant KV-HBM
block quotas are charged in the allocator; and a Guarantee admission
the pool cannot fund PREEMPTS an Opportunistic decode slot — the
victim's prompt + generated blocks retire into the prefix index, its
request re-queues at the front of its tenant's lane, and on
re-admission the trie match starts prefill at its first uncached token,
so the resumed stream is bit-exact with the unpreempted one (greedy and
sampled: the victim's remaining PRNG key schedule rides with the
re-queued request).  The radix cache is what makes preemption nearly
free: the only recomputed work is the sliding bucketed tail chunk.
"""

from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.decoding import _filter_logits, bucket_width
from ..models.transformer import TransformerConfig
from ..parallel.mesh import MeshSpec
from ..utils.logger import get_logger
from ..utils import profiling
from ..utils.promtext import (MetricFamily, MetricServer, Sample,
                              _format_value)
from .autotune import AnalyticPolicy, AutoTuner
from .drafter import NGramDrafter
from .kv_blocks import (BlockAllocator, BlockExhausted, QuotaExceeded,
                        init_conv_states, init_paged_pool,
                        init_retention_states, kind_blocks,
                        kv_row_layout, window_reserve_rows)
from .kv_tier import (DiskTier, HostTier, LRUTierPolicy, QoSTierPolicy,
                      WireCorruption, pack_block, unpack_block,
                      wire_block_bytes)
from . import paged
from .packed_args import PackedProgram
from .paged import (Recurrent, attend_path, experts_path, key_block_entries,
                    mixed_weight_passes, paged_copy_block, paged_decode_loop,
                    paged_decode_span, paged_diffusion_pass,
                    paged_diffusion_prefill, paged_mixed_diffusion_step,
                    paged_mixed_step,
                    paged_mixed_verify_step, paged_prefill_step,
                    paged_spec_loop, paged_upload_block,
                    paged_verify_span, tail_pages)
from .prefix_index import PrefixIndex
from . import stages
from .sharded import ShardedServingContext
from .qos import (DEFAULT_TENANT, QOS_GUARANTEE, QOS_OPPORTUNISTIC,
                  FairQueue, TenantRegistry, TenantSpec)

# TTFT histogram bucket upper bounds (seconds) for the metrics endpoint
# — spans sub-chunk CPU smoke latencies up to badly queued tail requests.
TTFT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                10.0)
# Inter-token-latency (time-between-tokens) bucket bounds: an order of
# magnitude finer than TTFT — a healthy decode lane emits every few ms,
# and the tail the mixed scheduler exists to fix (a lane stalled behind
# a multi-chunk prompt) shows up in the 100ms..1s slots.
TBT_BUCKETS = (0.0002, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
               0.1, 0.25, 0.5, 1.0)
# Speculative acceptance-ratio bucket bounds: per verify round,
# accepted drafts / drafted — always in [0, 1], so the +Inf tail stays
# structurally empty and the top bucket counts full-accept rounds.
SPEC_ACCEPT_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)

# Speculative device-loop statics (device residency v2).  The on-device
# drafting window: each lane carries its most recent SPEC_LOOP_HIST
# emitted tokens as right-aligned loop state, the device n-gram
# proposer's lookup universe (drafts are scheduling-only — verification
# is exact-match against the engine's own picks, so a bounded window
# changes acceptance RATE, never streams).  The re-draft threshold: a
# unit whose drafting lanes accept below this fraction of their
# AGGREGATE proposals exits the loop at that span boundary — the
# host's adaptive width controller (EMA halving) gets to observe the
# collapse instead of the device grinding K units of misses, while a
# single cold lane cannot end the launch for the whole batch.
SPEC_LOOP_HIST = 64
SPEC_LOOP_REDRAFT = 0.25

# A gated dispatch is SLOW when it lasts longer than both of these: the
# engine then says where the seconds went (guard.acquire, launch or
# device_wait) on one WARNING line and counts it in
# kubeshare_serving_slow_dispatches_total{phase}.  The factor is over the
# running estimate of a dispatch's length (the guard's own 0.8/0.2 average
# of the same elapsed times, kept here because a guard may be a proxy).
SLOW_DISPATCH_S = 1.0
SLOW_DISPATCH_FACTOR = 8.0
# device arrays at the head of each kind of in-flight record — what
# _consume_inflight fetches before its bookkeeping
_INFLIGHT_DEVICE_ARRAYS = {"span": 1, "verify": 2, "loop": 2,
                           "spec_loop": 5, "diffusion": 2}


def _pow2_ceil(n: int) -> int:
    """Smallest power of two >= n (n >= 1) — verify dispatch widths are
    bucketed like prefill chunks, so ragged draft lengths hit the
    warmed shape set instead of compiling one shape per length."""
    return 1 << (n - 1).bit_length() if n > 1 else 1


def _bucket_observe(counts: List[int], seconds: float,
                    bounds=TTFT_BUCKETS, n: int = 1) -> None:
    """Add ``n`` observations of ``seconds`` to the ``bounds``
    histogram slot covering it (last slot is the +Inf tail)."""
    for i, le in enumerate(bounds):
        if seconds <= le:
            counts[i] += n
            return
    counts[-1] += n


def _histogram_samples(family: MetricFamily, name: str, labels: Dict[str, str],
                       counts: List[int], total: float,
                       bounds=TTFT_BUCKETS) -> None:
    """Append one Prometheus histogram series (cumulative buckets +
    sum + count) over ``bounds`` to ``family``."""
    cum = 0
    for le, count in zip(bounds, counts):
        cum += count
        family.samples.append(Sample(
            f"{name}_bucket", {**labels, "le": _format_value(le)}, cum))
    cum += counts[-1]
    family.samples.append(Sample(
        f"{name}_bucket", {**labels, "le": "+Inf"}, cum))
    family.samples.append(Sample(f"{name}_sum", labels, total))
    family.samples.append(Sample(f"{name}_count", labels, cum))


def plan_prefill_chunks(
    prompt_len: int, chunk: int, max_len: int, start: int = 0
) -> Tuple[List[Tuple[int, int, int]], int]:
    """Split a prompt into (start, width, last_row) chunks of bucketed
    widths; returns (plan, cover) where ``cover`` is the highest cache
    row the plan writes + 1 (never past ``max_len``, the slot's row
    bound — a short pool must not pad past the rows a request may own).

    ``start`` is the first token that actually needs prefilling (the
    prefix cache's match length, 0 when cold): full-width chunks tile
    ``start ..``; the ragged tail becomes ONE bucketed chunk that ENDS
    exactly at the prompt's last token by sliding its start back over
    already-written positions — possibly below ``start``, into cached
    rows: the recompute is deterministic, so the overwrite == no-op
    (identical tokens at identical positions yield identical K/V).
    Only a prompt shorter than its own bucket pads forward from 0; its
    pad rows are dead (outputs discarded, K/V overwritten by decode's
    write-then-attend order before any causal band reaches them).
    """
    if not 0 <= start < prompt_len:
        raise ValueError(
            f"start {start} not in 0..{prompt_len - 1} (at least one "
            f"prompt token must prefill to produce first-token logits)")
    n, r = divmod(prompt_len - start, chunk)
    plan = [(start + i * chunk, chunk, chunk - 1) for i in range(n)]
    cover = start + n * chunk
    if r:
        width = min(bucket_width(r, chunk), max_len)
        if prompt_len >= width:
            plan.append((prompt_len - width, width, width - 1))
            cover = prompt_len
        else:  # whole prompt under its bucket: pad the tail; logits row
            plan = [(0, width, prompt_len - 1)]  # is the last REAL token
            cover = width
    return plan, cover


@dataclass(frozen=True)
class EngineConfig:
    """Static serving-pool geometry.  ``num_slots`` bounds in-flight
    requests; ``num_blocks``/``block_size`` size the KV pool (HBM =
    num_blocks x bytes_per_block; the cells' sizes: PERF.md section 4);
    ``max_request_len`` bounds prompt + generation per request and fixes
    the block-table width."""

    num_slots: int = 8
    block_size: int = 16
    num_blocks: int = 129  # 128 allocatable + scratch block 0
    max_request_len: int = 256
    prefill_chunk: int = 32
    # decode steps fused into ONE dispatch (a lax.scan inside the jitted
    # step): amortizes per-step dispatch/launch overhead the way the
    # PyGraph line of work does for GPU graphs — the decode math is
    # step-identical, lanes self-deactivate mid-span on budget/EOS, so
    # equivalence survives any span.  1 = dispatch per token.
    decode_span: int = 4
    # DEVICE-RESIDENT MULTI-STEP LOOP: fuse up to K consecutive decode
    # scheduler iterations into ONE compiled launch (a lax.while_loop
    # of span-units, each the exact decode-span scan).  Emissions ring-
    # buffer on device; the loop exits early at a span boundary the
    # moment any lane deactivates (budget/EOS), so the host only runs
    # the planner at admission/retire/preemption boundaries — planner
    # invocations per emitted token drop ~K x on decode-heavy phases.
    # Streams are bit-exact with K=1 by construction (the loop is
    # consecutive identical decode plans batched into one launch).
    # Must be a power of two >= 1; 1 = one plan per launch (off).
    steps_per_launch: int = 1
    eos_token: Optional[int] = None
    # sampling restriction set, engine-wide (temperature rides per
    # request; the filter set is part of the compiled step)
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    # radix-tree prefix caching over the block pool: retired prompts'
    # blocks are indexed and shared with later requests (refcounted,
    # copy-on-write on mid-block divergence, LRU-evicted only when a
    # reservation would otherwise fail).  Output is bit-exact either
    # way; False buys back nothing but is the tests' reference arm.
    prefix_cache: bool = True
    # stall-free mixed batching: when prefill and decode work coexist,
    # fuse ONE bounded prefill chunk into the decode dispatch instead
    # of stalling every decode lane behind the prompt (the either/or
    # Orca discipline's tail-latency cost).  Streams are bit-exact
    # either way; False is the tests' reference arm and restores strict
    # prefill priority.
    mixed: bool = True
    # KV cache tiering (kv_tier.py): a host-RAM byte budget for demoted
    # prefix blocks.  None = tiering off (evicted prefixes are
    # destroyed, the pre-tier behavior); set, the allocator's eviction
    # path SERIALIZES victims into the host tier instead, the trie
    # keeps their nodes HOST-resident, and admission promotes matched
    # host blocks back into fresh device blocks through one warmed
    # compiled upload shape.  Streams are bit-exact either way.
    # Requires prefix_cache.
    host_tier_bytes: Optional[int] = None
    # which TierPolicy drives demote-vs-drop and host victim order:
    # "lru" (demote all, evict coldest) or "qos" (tenant-aware —
    # Guarantee-charged host bytes are protected from Opportunistic
    # pressure, Guarantee pressure drains Opportunistic entries first)
    tier_policy: str = "lru"
    # DISK tier below host RAM (kv_tier.DiskTier): a byte budget for
    # the mmap-backed arena host-budget evictions cascade into
    # (HOST→DISK) instead of being destroyed.  Admission stages a
    # matched disk block back up (DISK→HOST, crc-validated) and the
    # existing paged_upload_block promotion takes it from there.  None
    # = off (host evictions destroy, the pre-disk behavior).  Requires
    # host_tier_bytes — the cascade has to have a tier above it.
    # Streams are bit-exact either way.
    disk_tier_bytes: Optional[int] = None
    # arena file path for the disk tier (None = an anonymous unlinked
    # tempfile).  A named path is what an exported prefix store reads
    # across a process boundary (fabric.export_prefix_store).
    disk_tier_path: Optional[str] = None
    # per-step cap on the prefill tokens fused into a mixed dispatch —
    # the bound on the extra latency ANY decode lane (a Guarantee
    # tenant's included) pays per admission ride-along.  A plan chunk
    # wider than the budget is sliced to its leading largest-power-of-
    # two piece <= budget (an already-warmed bucket width, so slicing
    # never compiles a new shape).  None = prefill_chunk (whole chunks
    # fuse, nothing is sliced).
    mixed_prefill_budget: Optional[int] = None
    # SPECULATIVE DECODING (self-drafting, no second model): decode
    # lanes propose up to draft_len tokens by n-gram lookup over their
    # own prompt + generated history (serving/drafter.py) and ONE
    # width-W verify dispatch (paged.paged_verify_span) scores every
    # lane's proposals — the accepted prefix plus the correction pick
    # emits per dispatch.  Verification is exact-match against the
    # engine's own pick policy (greedy argmax / the categorical draw
    # under that emission's PRNG key), so streams are bit-exact with
    # speculation off BY CONSTRUCTION, greedy and sampled alike, and
    # the per-request key schedule is consumed identically.  False is
    # the tests' reference arm.
    speculative: bool = False
    # max drafted tokens per lane per verify round.  Must be a power of
    # two: the per-lane ADAPTIVE width (driven by a rolling acceptance
    # rate) doubles/halves within {1, 2, ..., draft_len}, so warmup
    # compiles O(log draft_len) verify shapes and nothing recompiles
    # mid-serve.
    draft_len: int = 4
    # the drafter's maximum n-gram order (longest suffix looked up)
    draft_ngram: int = 3
    # DISAGGREGATED serving role (serving/disagg.py): "both" is the
    # monolithic engine; "prefill" runs only prefill plan kinds and
    # hands finished prompts to a decode pool (its slots reserve only
    # the prompt-cover blocks — decode rows are never written there);
    # "decode" runs only decode/verify kinds and admits exclusively
    # through admit_migrated().  Role gating changes WHICH warmed
    # shapes exist and where a request's lifetime rows live, never the
    # emitted streams — the router hard-asserts bit-exactness against
    # a monolithic engine.
    pool_role: str = "both"
    # TENSOR-PARALLEL sharded serving (serving/sharded.py): a MeshSpec
    # with dp=ep=sp=1 and tp>1 stands up a serving mesh — params shard
    # Megatron-style, the KV pool head-shards, and every dispatch above
    # runs as ONE shard_map program with the collectives inside, so the
    # dispatch counts (and the zero-recompile warmup contract) are
    # unchanged by the device count.  Streams are BIT-EXACT with the
    # single-device engine (sharded.py's no-partial-sums construction),
    # greedy and sampled, so None vs a mesh is the tests' reference pair.
    mesh_spec: Optional[MeshSpec] = None
    # route prefill chunks at/above this width through the Ulysses
    # sequence-parallel attention re-shard inside the sharded program
    # (heads are few and rows are many in a long chunk, so splitting
    # query time beats splitting heads).  None = always head-parallel.
    # Requires mesh_spec; bit-exact either way (test-locked).
    long_context_threshold: Optional[int] = None
    # ONLINE AUTOTUNING (serving/autotune.py): retune the RECOMPILE-
    # FREE knob subset every autotune_interval scheduler steps — the
    # fused-prefill budget (within the warmed chunk universe, which is
    # warmed in FULL under autotune so the budget can move both ways),
    # the effective device-loop depth (among warmed loop-K shapes; the
    # configured steps_per_launch is the ceiling), and the per-lane
    # draft-width cap (cost-model expected tokens-per-dispatch in
    # place of the fixed EMA doubling rule).  Every knob is
    # scheduling-only: streams are bit-exact tuner-on vs tuner-off and
    # compile counts stay fixed after warmup (test-locked); a plugged
    # TuningPolicy is sandboxed to the warmed-shape envelope.
    autotune: bool = False
    autotune_interval: int = 32
    # PENDING-LANE ADMISSION RING (device residency v2): the number of
    # queued requests the engine pre-admits and pre-prefills ahead of a
    # speculative device-loop launch.  The ring rides into the launch as
    # pre-marshaled lane state (block table, budget, PRNG key schedule,
    # drafting window); when a lane retires at a span boundary INSIDE
    # the loop, the device activates the next ring entry in place — an
    # admission costs a ring write instead of a loop exit + replan +
    # relaunch.  0 = off (a retirement ends the launch).  Requires
    # speculative=True, steps_per_launch > 1, and pool_role="both"
    # (the host-side fill runs this pool's own prefill path).
    admission_ring: int = 0


def _warmed_prefill_widths(ec: EngineConfig, floor: int = 0) -> set:
    """The prefill-chunk bucket universe warmup compiles (and the
    autotuner's fused-budget envelope): the configured chunk plus every
    smaller power of two, capped at the slot row bound so a short pool
    folds over-wide buckets into one max_request_len-wide shape.  Empty
    on a decode-role pool — no prefill shape ever dispatches there.
    No bucket narrower than ``floor`` is ever planned: under generation by
    diffusion over blocks a chunk is whole blocks of ``diffusion_block``
    rows, and a 'retention' block pads a prompt's last chunk forward to
    whole pages (:func:`_bucket_floor`)."""
    widths = {ec.prefill_chunk}
    w = max(1, floor)
    while w < ec.prefill_chunk:
        widths.add(w)
        w *= 2
    widths = {min(w, ec.max_request_len) for w in widths}
    return set() if ec.pool_role == "decode" else widths


def _bucket_floor(ec: EngineConfig, config: TransformerConfig) -> int:
    """The narrowest prefill bucket a configuration plans."""
    if _carries_state(config):
        return min(ec.block_size, ec.prefill_chunk)
    return config.diffusion_block


def _carries_state(config: TransformerConfig) -> bool:
    """The model's lanes hold a state BY SLOT beside the paged pool, which
    every step program carries (``paged.Recurrent``): a 'retention' block's
    recurrent states, the short convolutions' windows."""
    return config.block == "retention" or config.conv_layers > 0


def _config_rows(ec: EngineConfig, config: TransformerConfig,
                 mesh_devices=None, shared_host_tier=None):
    """The engine-config validation table: ``(failed, message)`` rows
    checked in order by :class:`ServingEngine`, consolidating what used
    to be a scatter of inline raises — every interacting-knob
    constraint (and its loud message) is visible and extendable in ONE
    place, and a new knob adds a row instead of another branch."""
    b = config.diffusion_block
    widths = _warmed_prefill_widths(ec, _bucket_floor(ec, config))
    min_piece = min(widths) if widths else 1
    wire = (wire_block_bytes(
        ec.block_size, config.n_layers, config.kv_heads,
        ec.block_size, config.head_dim,
        jnp.dtype(config.dtype).itemsize)
        if ec.host_tier_bytes is not None else None)
    layout = kv_row_layout(config)
    # what still assumes a K and a V [kv_heads, head_dim] pair a layer
    not_served = [name for name, asked in (
        ("speculative=True (the draft-verify programs)", ec.speculative),
        ("steps_per_launch > 1 (the device-resident loops)",
         ec.steps_per_launch > 1),
        ("mesh_spec (serving/sharded.py shards the KV-head axis)",
         ec.mesh_spec is not None),
        ("host_tier_bytes (serving/kv_tier.py packs K/V head slabs)",
         ec.host_tier_bytes is not None),
        ("a shared host tier (serving/kv_tier.py, serving/fabric.py)",
         shared_host_tier is not None),
        (f"pool_role={ec.pool_role!r} (serving/disagg.py migrates K/V "
         f"head slabs)", ec.pool_role != "both"),
    ) if asked]
    # what still assumes that a dispatch yields one token a lane, causal,
    # from the last token served
    no_diffusion = [name for name, asked in (
        ("speculative=True (a pass commits rows by confidence: there is "
         "no draft to verify)", ec.speculative),
        ("steps_per_launch > 1 (the device-resident loops advance one "
         "token a lane a step)", ec.steps_per_launch > 1),
        ("mesh_spec (serving/sharded.py has no twin of the diffusion "
         "pass)", ec.mesh_spec is not None),
        ("host_tier_bytes (a demoted page may hold rows of an unfinished "
         "block)", ec.host_tier_bytes is not None),
        ("a shared host tier (serving/kv_tier.py, serving/fabric.py)",
         shared_host_tier is not None),
        (f"pool_role={ec.pool_role!r} (serving/disagg.py hands over a "
         f"first token, and prefill yields none here)",
         ec.pool_role != "both"),
        ("autotune=True (the tuner's knobs are the span's and the "
         "drafts')", ec.autotune),
        ("eos_token (a block's rows are served out of order: nothing "
         "truncates a stream at a token yet)", ec.eos_token is not None),
    ) if asked]
    # what cannot roll a state by slot back, ship it or shard it: the one
    # list of a 'retention' block's recurrent states and of the short
    # convolutions' windows
    retention = config.block == "retention"
    stateful = _carries_state(config)
    no_state = [name for name, asked in (
        ("speculative=True (the draft-verify programs would have to roll "
         "a state back past the rejected rows)", ec.speculative),
        ("steps_per_launch > 1 (the device-resident loops carry no "
         "state and plan no fold)", ec.steps_per_launch > 1),
        ("mesh_spec (serving/sharded.py has no twin of the state's "
         "programs)", ec.mesh_spec is not None),
        ("host_tier_bytes (serving/kv_tier.py packs K/V pages; no tier "
         "holds a state, and a folded lane's pages are gone)",
         ec.host_tier_bytes is not None),
        ("a shared host tier (serving/kv_tier.py, serving/fabric.py)",
         shared_host_tier is not None),
        (f"pool_role={ec.pool_role!r} (serving/disagg.py migrates K/V "
         f"pages, not a state)", ec.pool_role != "both"),
        ("autotune=True (the tuner re-slices chunks and arms the loops)",
         ec.autotune),
    ) if asked]
    # what cannot hold a cache by layer kind (a table, a pool and an
    # allocator a kind; a window kind's pages go back while the request
    # runs): everything that takes a lane's ONE chain of pages for its
    # whole context.  The prefix index and its copy-on-write matches are
    # not in the list: such an engine keeps no index (as one with a state
    # by slot), whatever prefix_cache says
    no_kinds = [name for name, asked in (
        ("speculative=True (the draft-verify programs write and roll back "
         "one table's rows)", ec.speculative),
        ("steps_per_launch > 1 (the device-resident loops run past the "
         "dispatch whose end hands a window's pages back)",
         ec.steps_per_launch > 1),
        ("mesh_spec (serving/sharded.py's twins shard one pool's KV-head "
         "axis)", ec.mesh_spec is not None),
        ("host_tier_bytes (serving/kv_tier.py packs a page of every "
         "layer; a window layer's is gone)", ec.host_tier_bytes is not None),
        ("a shared host tier (serving/kv_tier.py, serving/fabric.py)",
         shared_host_tier is not None),
        (f"pool_role={ec.pool_role!r} (serving/disagg.py migrates one "
         f"chain of pages a request)", ec.pool_role != "both"),
        ("autotune=True (the tuner arms the loops and re-slices chunks "
         "past what a window lane is funded for)", ec.autotune),
    ) if asked]
    key_block = paged.KEY_BLOCK
    return [
        (bool(layout.window_layers) and bool(no_kinds),
         f"this model caches BY LAYER KIND ({layout.window_layers} of its "
         f"{layout.layers} attention layers keep a window of "
         f"{config.attention_window} rows in a pool of their own, under a "
         f"table and an allocator of their own), which is not served yet "
         f"by: {'; '.join(no_kinds)}"),
        (stateful and bool(no_state),
         f"block {config.block!r} serves a state a lane BY SLOT beside its "
         f"paged rows ("
         + ("a recurrent state beside a tail of unfolded rows"
            if retention else "the short convolutions' windows")
         + f"), which is not served yet by: {'; '.join(no_state)}"),
        (retention and (key_block % ec.block_size != 0
                        or ec.prefill_chunk > key_block
                        or ec.decode_span > key_block),
         f"block 'retention' folds a key block of {key_block} rows at a "
         f"time: block_size {ec.block_size} must divide it (a fold frees "
         f"whole pages), and prefill_chunk {ec.prefill_chunk} and "
         f"decode_span {ec.decode_span} may not exceed it (a dispatch "
         f"completes at most one key block a lane)"),
        (bool(b) and bool(no_diffusion),
         f"diffusion_block {b}: generation by diffusion over blocks (a "
         f"lane holds a block state and a pass commits 0..{b} tokens) is "
         f"not served yet by: {'; '.join(no_diffusion)}"),
        (bool(b) and (bool(b & (b - 1)) or ec.block_size % b != 0
                      or ec.prefill_chunk % b != 0),
         f"diffusion_block {b} must be a power of two that divides "
         f"block_size {ec.block_size} and prefill_chunk "
         f"{ec.prefill_chunk}: a prefill chunk, a page and a prefix "
         f"match are whole diffusion blocks"),
        (bool(b) and ec.mixed_prefill_budget is not None
         and ec.mixed_prefill_budget < b,
         f"mixed_prefill_budget {ec.mixed_prefill_budget} is below one "
         f"diffusion block of {b} rows — a fused chunk is whole blocks"),
        (config.block == "gqa_moe" and not b and ec.mesh_spec is not None,
         "block 'gqa_moe' is not served yet by mesh_spec: "
         "serving/sharded.py shards the dense block's parameters"),
        (layout.kind != "kv_heads" and bool(not_served),
         f"block {config.block!r} caches the {layout.kind!r} row layout "
         f"(one row of {layout.k_row[1]} + {layout.v_row[1]} values a "
         f"sub-layer, no heads), which is not served yet by: "
         f"{'; '.join(not_served)}"),
        (mesh_devices is not None and ec.mesh_spec is None,
         "mesh_devices requires mesh_spec — an unsharded engine "
         "has no mesh to pin onto a device group; pin it with "
         "jax.default_device + device_put instead (the fleet's "
         "tp=1 build path does exactly that)"),
        (ec.max_request_len > config.max_seq_len,
         f"max_request_len {ec.max_request_len} exceeds the model's "
         f"max_seq_len {config.max_seq_len}"),
        (ec.prefill_chunk < 1,
         f"prefill_chunk must be >= 1, got {ec.prefill_chunk}"),
        (ec.decode_span < 1,
         f"decode_span must be >= 1, got {ec.decode_span}"),
        (ec.steps_per_launch < 1
         or bool(ec.steps_per_launch & (ec.steps_per_launch - 1)),
         f"steps_per_launch must be a power of two >= 1, got "
         f"{ec.steps_per_launch} — the loop warms exactly one "
         f"shape per config, and power-of-two K keeps the knob "
         f"space aligned with the other fused widths"),
        (ec.steps_per_launch > 1 and ec.pool_role == "prefill",
         f"steps_per_launch {ec.steps_per_launch} is meaningless "
         f"on a prefill-role pool — it never runs decode plans, "
         f"so the device loop would silently never fire; set "
         f"steps_per_launch=1"),
        (ec.mixed_prefill_budget is not None
         and ec.mixed_prefill_budget < 1,
         f"mixed_prefill_budget must be >= 1 or None, got "
         f"{ec.mixed_prefill_budget}"),
        (ec.mixed and ec.mixed_prefill_budget is not None
         and ec.mixed_prefill_budget < min_piece,
         f"mixed_prefill_budget {ec.mixed_prefill_budget} is below "
         f"the smallest warmed chunk piece ({min_piece}) — no fused "
         f"chunk could ever be sliced to fit, so prefill would "
         f"silently starve behind decode"),
        (ec.host_tier_bytes is not None and not ec.prefix_cache,
         "host_tier_bytes requires prefix_cache=True — the tier "
         "spills the radix index; there is nothing to spill "
         "without it"),
        (ec.host_tier_bytes is not None and wire is not None
         and ec.host_tier_bytes < wire,
         f"host_tier_bytes {ec.host_tier_bytes} is below one "
         f"block's wire size ({wire}) — the tier could "
         f"never hold a single block"),
        (ec.tier_policy not in ("lru", "qos"),
         f"tier_policy must be 'lru' or 'qos', got "
         f"{ec.tier_policy!r}"),
        (ec.disk_tier_bytes is not None and ec.host_tier_bytes is None,
         "disk_tier_bytes requires host_tier_bytes — the disk tier "
         "is the cascade target of host-budget evictions; there is "
         "no HOST→DISK demotion without a host tier above it"),
        (ec.disk_tier_bytes is not None and wire is not None
         and ec.disk_tier_bytes < wire,
         f"disk_tier_bytes {ec.disk_tier_bytes} is below one "
         f"block's wire size ({wire}) — the disk tier could "
         f"never hold a single block"),
        (ec.disk_tier_path is not None and ec.disk_tier_bytes is None,
         "disk_tier_path without disk_tier_bytes — a named arena "
         "file needs a disk tier to fill it"),
        (ec.draft_len < 1 or bool(ec.draft_len & (ec.draft_len - 1)),
         f"draft_len must be a power of two >= 1, got "
         f"{ec.draft_len} — the adaptive width doubles/halves "
         f"within the warmed power-of-two verify shape set"),
        (ec.draft_ngram < 1,
         f"draft_ngram must be >= 1, got {ec.draft_ngram}"),
        (ec.pool_role not in ("both", "prefill", "decode"),
         f"pool_role must be 'both', 'prefill' or 'decode', got "
         f"{ec.pool_role!r}"),
        (ec.pool_role != "both" and ec.mixed,
         f"pool_role {ec.pool_role!r} excludes mixed batching — "
         f"a single-phase pool has no prefill+decode coexistence "
         f"to fuse; set mixed=False"),
        (shared_host_tier is not None and ec.host_tier_bytes is not None,
         "shared_host_tier and host_tier_bytes are mutually "
         "exclusive — the disagg router owns the shared tier's "
         "budget"),
        (shared_host_tier is not None and not ec.prefix_cache,
         "shared_host_tier requires prefix_cache=True — the tier "
         "spills the radix index; there is nothing to spill "
         "without it"),
        (ec.long_context_threshold is not None and ec.mesh_spec is None,
         "long_context_threshold requires mesh_spec — the "
         "Ulysses route is a re-shard inside the sharded "
         "program; a single-device engine has nothing to route"),
        (ec.autotune_interval < 1,
         f"autotune_interval must be >= 1, got "
         f"{ec.autotune_interval} — the tuner ticks once per "
         f"scheduler step and retunes every interval-th tick"),
        (ec.admission_ring < 0,
         f"admission_ring must be >= 0, got {ec.admission_ring}"),
        (ec.admission_ring > 0 and (not ec.speculative
                                    or ec.steps_per_launch <= 1
                                    or ec.pool_role != "both"),
         f"admission_ring {ec.admission_ring} requires "
         f"speculative=True, steps_per_launch > 1 and "
         f"pool_role='both' — the ring is consumed only inside the "
         f"speculative device loop, and its host-side fill runs this "
         f"pool's own prefill path"),
    ]


@dataclass
class Request:
    """One generation request.  ``temperature == 0`` is greedy;
    sampled requests must carry their own PRNG ``rng`` (the engine
    consumes keys exactly like ``sample_decode_with_cache``, so a
    single-slot engine reproduces it bit-for-bit).  ``tenant`` names a
    registered :class:`~kubeshare_tpu.serving.qos.TenantSpec`; the
    default registry has one uncapped Guarantee tenant, so single-tenant
    callers never touch QoS."""

    rid: str
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float = 0.0
    rng: Optional[jax.Array] = None
    tenant: str = DEFAULT_TENANT


@dataclass
class _Pending:
    """A queued (or preempted-and-requeued) request with everything
    admission needs precomputed.  Fresh submissions carry ``rng`` and
    derive their key schedule at first admission; a RESUMED entry
    carries the remaining schedule explicitly (``first_key`` +
    ``step_keys``) plus the tokens already emitted, so the continuation
    consumes exactly the keys the unpreempted run would have."""

    rid: str
    tenant: str
    prompt: np.ndarray
    max_new: int
    temperature: float
    plan: List[Tuple[int, int, int]]
    needed: int
    rng: Optional[jax.Array] = None
    first_key: Optional[np.ndarray] = None
    step_keys: Optional[np.ndarray] = None
    emitted: List[int] = field(default_factory=list)
    # a RESUMED entry's last pre-preemption emission time: the gap to
    # the continuation's first token is a real inter-token stall and
    # must land in the TBT histogram (the metric exists for that tail)
    last_token_at: Optional[float] = None
    # a RESUMED diffusion lane's unfinished block, as it stood: the
    # passes over it left nothing in the pool, so the state is all of it
    block: Optional["_BlockState"] = None


@dataclass
class _BlockState:
    """The block a diffusion lane is denoising: rows ``slot.length ..
    slot.length + B - 1``.  ``tokens`` [B] holds what is known (the
    prompt's tail, the rows committed so far); ``masked`` [B] says which
    rows are still unknown — position state, never ``token ==
    mask_token``; ``open`` [B] which of them may be committed (a row
    past the request's budget stays masked for good); ``step`` counts
    the denoising passes the block has had, ``served`` the tokens it has
    served."""

    tokens: np.ndarray
    masked: np.ndarray
    open: np.ndarray
    step: int = 0
    served: int = 0


@dataclass
class _PrefixHit:
    """One admission's prefix-cache match, tier-aware.  ``start`` is
    the first token that must prefill; ``shared`` are DEVICE-resident
    fully matched blocks (retained and mapped for the request's
    lifetime); ``promote`` are HOST-resident fully matched trie nodes
    whose payloads upload into the leading freshly reserved blocks
    (rebound device-resident, shared from then on); exactly one of
    ``cow_src`` (device partial match — CoW dispatch) / ``host_cow``
    (host partial match — payload uploaded straight into the private
    tail block, entry stays host-side for other matchers) may be set.
    ``needed`` counts the reservation: promoted + private tail + fresh
    suffix blocks.  ``host_tokens`` is the prompt-token count recovered
    from host-resident blocks (the tier-hit metric)."""

    start: int
    shared: List[int]
    cow_src: Optional[int]
    promote: List
    host_cow: Optional[object]
    plan: List[Tuple[int, int, int]]
    needed: int
    host_tokens: int


@dataclass
class RequestResult:
    rid: str
    prompt_len: int
    tokens: List[int] = field(default_factory=list)
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None
    # the launch of the first dispatch that carried a prefill chunk of
    # this request (a prompt the prefix cache covers but for its tail has
    # that one), and how many dispatches carried one; None / 0 for a
    # request a decode pool admitted already prefilled
    first_dispatch_at: Optional[float] = None
    prefill_chunks: int = 0
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None

    @property
    def done(self) -> bool:
        return self.finished_at is not None

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at


@dataclass
class _StepPlan:
    """ONE scheduling decision, separated from dispatch mechanics:
    :meth:`ServingEngine._plan_step` decides which lanes prefill /
    decode / verify this step and at what widths, and
    :meth:`ServingEngine._dispatch_plan` only builds device arguments
    and launches.  ``kind`` selects the dispatch — "prefill" (one
    standalone chunk), "decode" (the plain span), "verify" (the
    speculative draft-verify chunk), "mixed" / "mixed_verify" (the
    fused prefill + decode-phase programs), and for a configuration that
    generates by diffusion over blocks "diffusion" / "mixed_diffusion"
    (one pass over every lane's block, alone or beside a chunk).
    ``drafts`` maps slot index
    to that lane's proposed tokens; ``verify_width`` is the dispatch
    width W = 1 + the power-of-two-bucketed max draft length (a warmed
    shape by construction)."""

    kind: str
    prefill_slot: Optional["_Slot"] = None
    chunk: Optional[Tuple[int, int, int]] = None
    decode_slots: List["_Slot"] = field(default_factory=list)
    drafts: Dict[int, List[int]] = field(default_factory=dict)
    verify_width: int = 0


class _Slot:
    __slots__ = (
        "idx", "state", "rid", "blocks", "table", "length", "generated",
        "prompt", "plan", "max_new", "temperature", "first_key",
        "step_keys", "result", "tenant", "emitted_prefix",
        "last_token_at", "drafter", "draft_width", "accept_rate",
        "block", "folded", "paged_to", "window_blocks", "window_to",
    )

    def __init__(self, idx: int, table_width: int) -> None:
        self.idx = idx
        self.state = "free"  # free | prefill | decode
        self.table = np.zeros(table_width, np.int32)
        self._clear()

    def _clear(self) -> None:
        self.rid = ""
        self.blocks: List[int] = []
        self.table[:] = 0  # every entry back to the scratch block
        self.length = 0
        self.generated: List[int] = []
        self.prompt = None
        self.plan: List[Tuple[int, int, int]] = []
        self.max_new = 0
        self.temperature = 0.0
        self.first_key = None
        self.step_keys = None
        self.result: Optional[RequestResult] = None
        self.tenant = DEFAULT_TENANT
        # tokens emitted in earlier incarnations of a preempted request;
        # prepended to slot.generated at retirement
        self.emitted_prefix: List[int] = []
        # wall time the slot's newest token became host-visible — the
        # inter-token-latency histogram's reference point
        self.last_token_at: Optional[float] = None
        # speculative state (engine_config.speculative): the lane's
        # n-gram drafter, its current adaptive draft width (a power of
        # two in 1..draft_len), and the rolling acceptance-rate EMA
        # driving the width.  Rebuilt at (re-)admission — a resumed
        # lane's drafter window is prompt + generated, identical to the
        # unpreempted lane's.
        self.drafter: Optional[NGramDrafter] = None
        self.draft_width = 0
        self.accept_rate = 0.5
        # a diffusion lane's current block (a resumed one's rides in
        # from its _Pending until the prefill is done); ``generated``
        # then holds the finished blocks' tokens only
        self.block: Optional[_BlockState] = None
        # a 'retention' lane: the rows it has folded into its slot's
        # state (a multiple of the key block; its pages begin there) and
        # the table entries it has been given pages for so far
        self.folded = 0
        self.paged_to = 0
        # a lane of a cache by layer kind: the window kind's pages it
        # holds, in the order of the entries they fill of that kind's table
        # (the second half of ``table``) — the ``len(window_blocks)``
        # entries that end at ``window_to``; the entries behind have been
        # handed back, those ahead are not drawn yet
        self.window_blocks: List[int] = []
        self.window_to = 0


def _program_name(kind: str) -> str:
    """``kubeshare_<kind>_step``: the XLA module is then
    ``jit_kubeshare_<kind>_step``, and the device plane's ``XLA Modules``
    line tells the engine's programs from everything else on the chip (a
    user's ``jit_step``, pod B's).  The name is in the compile cache's key."""
    return f"kubeshare_{kind}_step"


def _step_program(kind: str, fn, donate_argnums, carried=None):
    """``fn(params, pool_k, pool_v, *arguments)`` as the step program
    ``kubeshare_<kind>_step``, called as ``fn`` is: the host arrays among
    ``arguments`` cross to the device as ONE buffer a call
    (``packed_args.PackedProgram``; ``carried`` hears what a call took
    over)."""
    return PackedProgram(_program_name(kind), fn, donate_argnums, carried)


def _block_program(kind: str, fn, donate_argnums):
    """A single-block pool write (``copy``, ``upload``) jitted under the
    step programs' names: its arguments are device scalars and slabs, so
    there is nothing to pack."""
    @functools.wraps(fn)  # keeps the argument names in the HLO
    def step(*args):
        return fn(*args)

    step.__name__ = step.__qualname__ = _program_name(kind)
    return jax.jit(step, donate_argnums=donate_argnums)


class ServingEngine:
    """Continuous-batching engine; see module docstring.

    Drive it with :meth:`submit` + :meth:`run` (drain everything) or
    :meth:`step` (one scheduling iteration — what a serving loop with
    live arrivals calls)."""

    def __init__(
        self,
        params,
        config: TransformerConfig,
        engine_config: Optional[EngineConfig] = None,
        guard=None,
        tenants: Optional[TenantRegistry] = None,
        pool_label: Optional[str] = None,
        shared_host_tier: Optional[HostTier] = None,
        tier_ledger_hook=None,
        replica_label: Optional[str] = None,
        mesh_devices=None,
        tuning_policy=None,
    ) -> None:
        ec = engine_config or EngineConfig()
        # the table-driven validation pass: every interacting-knob
        # constraint lives in _config_rows (one (failed, message) row
        # each), checked in order so the first violation raises with
        # its original loud message
        for failed, message in _config_rows(
                ec, config, mesh_devices=mesh_devices,
                shared_host_tier=shared_host_tier):
            if failed:
                raise ValueError(message)
        # fail fast on a bad filter set, like the dense sampling entries
        _filter_logits(jnp.zeros((1, 2)), ec.top_k, ec.top_p)
        # tensor-parallel mode: the context owns the mesh, the sharding
        # decision, parameter placement, and the shard_map twins the
        # step closures below swap in.  Built BEFORE the pool so the
        # pool buffers are committed to the KV sharding at allocation
        # (never materialized replicated first).
        self._sharded = (ShardedServingContext(
            config, ec.mesh_spec, params,
            long_context_threshold=ec.long_context_threshold,
            devices=mesh_devices)
            if ec.mesh_spec is not None else None)
        if self._sharded is not None:
            params = self._sharded.place_params(params)
        self.params = params
        self.model_config = config
        self.engine_config = ec
        self.guard = guard
        # the warmed prefill-chunk bucket universe — warmup compiles
        # exactly this set, and the autotuner's fused-budget envelope
        # is confined to it (a tuned budget can only select among
        # already-compiled shapes)
        self._warmed_widths = _warmed_prefill_widths(
            ec, _bucket_floor(ec, config))
        # a cache BY LAYER KIND (a layer names the "window" kind): a pool,
        # a table and an allocator a kind.  ``num_blocks`` stays the
        # pool's bytes in blocks of every layer's row, divided between the
        # kinds by what the engine can see (kv_blocks.kind_blocks); a lane
        # is funded for ``_window_pages`` pages of the window kind whatever
        # its length, and that kind's pages behind the window go back after
        # every dispatch (:meth:`_observe_kinds`)
        layout = kv_row_layout(config)
        self._kinds = layout.window_layers > 0
        kinds = None
        self._window_pages = 0
        if self._kinds:
            dispatch_rows = max(self._warmed_widths | {ec.decode_span})
            kinds = kind_blocks(
                layout, ec.num_blocks, ec.block_size, ec.max_request_len,
                dispatch_rows, config.attention_window)
            self._window_pages = window_reserve_rows(
                config.attention_window, ec.block_size,
                dispatch_rows) // ec.block_size
        self.pool = init_paged_pool(
            config, ec.num_blocks, ec.block_size,
            kv_sharding=(self._sharded.kv_sharding
                         if self._sharded is not None else None),
            kinds=kinds)
        # a 'retention' block: the lanes' recurrent states, BY SLOT and
        # beside the pool (kv_blocks.init_retention_states); the pool
        # holds a lane's unfolded rows only.  A model whose layers name
        # the short convolution: its windows, by slot likewise
        # (kv_blocks.init_conv_states), beside the attention layers'
        # rows.  No snapshot of a state exists at a page boundary, so a
        # prefix match could give nothing (it would need the state AT the
        # matched row): such an engine keeps no prefix index
        self._retention = config.block == "retention"
        self._conv = config.conv_layers > 0
        self._stateful = _carries_state(config)
        # a mixed dispatch's chunk rides the span's first pass over the
        # layer stack — a model's with a state by slot too — unless the
        # sharded context's own composition keeps it a pass of its own
        self._mixed_fused = self._sharded is None
        self._mixed_passes = mixed_weight_passes(ec.decode_span,
                                                 not self._mixed_fused)
        self.states = (init_retention_states(config, ec.num_slots)
                       if self._retention else
                       init_conv_states(config, ec.num_slots)
                       if self._conv else None)
        # nor does a cache by layer kind: a match would need the window
        # layers' pages of the matched rows, which went back long ago
        self.prefix_index = (PrefixIndex(ec.block_size)
                             if ec.prefix_cache and not self._stateful
                             and not self._kinds else None)
        # the tenant registry must exist before the tier policy (the
        # QoS-aware policy reads class membership from it)
        self.tenants = tenants or TenantRegistry.default()
        self.host_tier: Optional[HostTier] = None
        self.disk_tier: Optional[DiskTier] = None
        if ec.host_tier_bytes is not None:
            # the below-one-block's-wire-size check moved into the
            # _config_rows validation table with the rest
            policy = (LRUTierPolicy() if ec.tier_policy == "lru"
                      else QoSTierPolicy(self.tenants))
            self.host_tier = HostTier(ec.host_tier_bytes, policy,
                                      on_drop=self._spill_host_entry,
                                      ledger_hook=tier_ledger_hook)
            # the index purges a detached host descendant's tier entry
            # through this hook (evict of a device ancestor, displaced
            # leaf upgrades)
            self.prefix_index.host_drop = self.host_tier.forget
            if ec.disk_tier_bytes is not None:
                self.disk_tier = DiskTier(ec.disk_tier_bytes,
                                          path=ec.disk_tier_path,
                                          on_drop=self._drop_disk_entry)
                self.prefix_index.disk_drop = self.disk_tier.forget
        elif shared_host_tier is not None:
            # disaggregated mode: the router's one tier sits under BOTH
            # pools' tries (the cross-pool cache bus).  The router owns
            # on_drop (it must route an entry to whichever pool's trie
            # holds its node); this pool only needs forget wired so its
            # own detach paths purge entries it owns.
            self.host_tier = shared_host_tier
            self.prefix_index.host_drop = self.host_tier.forget
        self.allocator = BlockAllocator(
            self.pool.num_blocks, ec.block_size,
            evictor=(self._evict_blocks if self.prefix_index is not None
                     else None))
        self.window_allocator = (
            BlockAllocator(self.pool.kind_num_blocks[1], ec.block_size)
            if self._kinds else None)
        self._table_width = -(-ec.max_request_len // ec.block_size)
        # entries of a lane's table: a table a kind, side by side
        self._table_entries = self._table_width * (1 + self._kinds)
        # view rows a step of the blockwise attention takes (paged.py)
        self._key_block_rows = ec.block_size * key_block_entries(
            self._table_width, ec.block_size)
        self._slots = [_Slot(i, self._table_entries)
                       for i in range(ec.num_slots)]
        # mixed-batching scheduler state: the effective fused-chunk
        # budget, the prefill round-robin pointer (a many-chunk prompt
        # must not monopolize prefill ticks over later admissions), and
        # the one in-flight dispatch whose host-side effects are still
        # pending (read when consumed — see _consume_inflight)
        self._mixed_budget = (ec.mixed_prefill_budget
                              if ec.mixed_prefill_budget is not None
                              else ec.prefill_chunk)
        self._prefill_rr = 0
        self._inflight = None
        # beside it, a routed block's dispatch: (its counts array in a
        # list, the rows and the expert-layer passes it carried)
        self._routing_inflight = ([], 0, 0)
        # the rows a 'retention' lane is funded for: its unfolded tail
        # through the widest step, whatever the request's length
        self._tail_rows = (
            ec.block_size * tail_pages(
                max(self._warmed_widths | {ec.decode_span}), ec.block_size)
            if self._retention else 0)
        # beside the in-flight dispatch, what a 'retention' dispatch
        # carried (:meth:`_observe_retention` reads it in .consume), and
        # what one of a model with short convolutions did
        # (:meth:`_observe_conv`)
        self._retention_inflight: Optional[Dict] = None
        self._conv_inflight: Optional[Dict] = None
        # generation by diffusion over blocks: the block length B (0: one
        # token after another) and the rows a block commits at each of
        # its denoising passes
        self._diffusion = config.diffusion_block
        self._transfer = (config.transfer_counts() if self._diffusion
                          else ())
        # autotuner-owned scheduling state: the effective device-loop
        # depth (starts at the configured ceiling; the tuner moves it
        # among warmed loop-K shapes) and the per-lane draft-width cap
        # (starts uncapped at draft_len)
        self._loop_k = ec.steps_per_launch
        self._draft_width_cap = ec.draft_len
        # ...and the IN-LOOP draft-width cap (the spec loop's twin of
        # _draft_width_cap): bounds the device drafter's per-unit
        # proposal width inside a speculative launch.  Per-lane widths
        # are DATA to the one compiled spec-loop shape, so the tuner
        # moves this recompile-free.
        self._loop_draft_cap = ec.draft_len
        # pending-lane admission ring (device residency v2): requests
        # fully admitted and prefilled host-side, staged in detached
        # _Slot objects (idx -1) for in-loop activation.  The loop
        # binds one to a lane when that lane retires at a span
        # boundary; entries the loop never activated are bound to free
        # engine slots by _admit on the next step.
        self._ring_staged: List[_Slot] = []
        # admission queue: the QoS fair queue over _Pending entries
        # (plan + block count computed once at submit; _admit re-plans
        # only on a prefix-cache hit).  The default registry holds one
        # uncapped Guarantee tenant, making this exactly a FIFO.
        self._queue = FairQueue(self.tenants)
        self._results: Dict[str, RequestResult] = {}
        # disaggregation surface (serving/disagg.py): pool_label tags
        # this engine's metric families; the hooks are router-installed
        # seams — on_handoff(slot) fires at prefill completion instead
        # of entering decode, on_preempt_requeue(tenant, pending)
        # reroutes a preemption's resume entry (the router re-plans it
        # with PREFILL-pool geometry), on_tier_demote(node, payload,
        # tenant) mirrors a demoted block into the peer pool's trie.
        self.pool_label = pool_label
        # fleet surface (serving/fleet.py): replica_label tags this
        # engine's per-request metric families (dispatch/TTFT/TBT) so
        # the fleet's merged scrape stays per-replica attributable.
        self.replica_label = replica_label
        self.on_handoff = None
        self.on_preempt_requeue = None
        self.on_tier_demote = None
        # admission_gate() -> bool consulted before each queue pop: the
        # router's handoff backpressure (a prefill pool must not run
        # further ahead than the decode pool can absorb — a first token
        # with no decode capacity behind it is a stalled stream, not
        # progress).  None = admit whenever a slot and blocks exist.
        self.admission_gate = None
        # counters (the metrics endpoint's raw material, collect_metrics):
        # prefill_chunks / decode_steps / verify_steps count WORK UNITS
        # (chunks processed, spans/verify chunks run — standalone or
        # fused); mixed_steps / mixed_verify_steps count fused
        # dispatches, so standalone dispatch counts are
        # prefill_chunks - mixed_steps - mixed_verify_steps,
        # decode_steps - mixed_steps, and
        # verify_steps - mixed_verify_steps (a fused dispatch carries
        # exactly one prefill chunk and one decode-phase unit).
        self.decode_steps = 0
        self.prefill_chunks = 0
        self.mixed_steps = 0
        self.verify_steps = 0
        self.mixed_verify_steps = 0
        # passes over the layer stack the dispatched programs made, by
        # plan kind (:meth:`_weight_passes`; a loop's when it is consumed,
        # with its units)
        self.weight_passes: Dict[str, int] = {}
        # host arrays the launched programs' calls carried to the device
        # (one packed buffer a call: packed_args.py) and their bytes;
        # _launch_carried is the launch in hand's, for its span
        self.host_arg_transfers = 0
        self.host_arg_bytes = 0
        self._launch_carried = [0, 0]
        # device-resident loop counters: launches (fused dispatches)
        # and the span-units those launches actually ran.  Each unit is
        # one decode_span's worth of work and is absorbed into
        # decode_steps, so the standalone decode_span dispatch count
        # becomes decode_steps - mixed_steps - loop_units (a launch is
        # ONE dispatch covering loop_units/loop_launches units on
        # average — exactly the amortization the loop exists to buy)
        self.loop_launches = 0
        self.loop_units = 0
        # device residency v2 counters: speculative (verify-in-loop)
        # launches and the draft-verify units they ran (each unit is
        # one in-loop draft + width-W verify + acceptance round,
        # absorbed into verify_steps the way loop_units absorb into
        # decode_steps); loop exits by reason; and a realized-fusion-
        # depth summary (units per launch, BOTH loop kinds) so a reader
        # of the metrics endpoint (collect_metrics) gets depth directly
        # instead of dividing counters
        self.spec_loop_launches = 0
        self.spec_loop_units = 0
        self.loop_exit_reasons: Dict[str, int] = {
            "retire": 0, "budget": 0, "stop": 0, "redraft": 0,
            "ring_empty": 0}
        self.loop_depth_sum = 0
        self.loop_depth_count = 0
        # span-units covered by the most recent launch — the fleet's
        # dispatch watchdog scales its hang budget by this so a healthy
        # K-unit launch is never flagged hung
        self.last_launch_units = 1
        # host-overhead observability (the device loop's proof plane):
        # wall seconds per scheduling phase of step(), and the number
        # of planner invocations — the numerator and denominator a
        # reader of collect_metrics divides by emitted tokens
        self.host_seconds: Dict[str, float] = {
            "admit": 0.0, "plan": 0.0, "dispatch": 0.0, "consume": 0.0,
            "tune": 0.0}
        self.host_planner_invocations = 0
        # between _begin_launch and _dispatch: the plan being launched and
        # its open kubeshare.engine.marshal span
        self._launching: Optional[_StepPlan] = None
        self._marshal: Optional[profiling.span] = None
        # gated dispatches over SLOW_DISPATCH_S and SLOW_DISPATCH_FACTOR x
        # the running estimate, by the phase that took most of them
        self.slow_dispatches: Dict[str, int] = {
            "acquire": 0, "launch": 0, "device_wait": 0}
        self._dispatch_estimate_ms = 1.0
        self.log = get_logger("serving")
        # speculation counters, per tenant: proposals scored by verify
        # dispatches, drafts actually emitted, and the per-round
        # acceptance-ratio histogram ([bucket counts, ratio sum] —
        # the adaptive width controller's input, exported on the
        # metrics plane)
        self.spec_drafted: Dict[str, int] = {}
        self.spec_accepted: Dict[str, int] = {}
        self._spec_accept: Dict[str, list] = {}
        self.tokens_generated = 0
        # a diffusion configuration's passes: lane-passes by kind (a
        # denoising pass over a block with masked rows, the commit pass
        # over a finished one), the query rows they computed, the tokens
        # they served and the blocks they finished
        self.diffusion_passes: Dict[str, int] = {"denoise": 0, "commit": 0}
        self.diffusion_rows = 0
        self.diffusion_tokens_committed = 0
        self.diffusion_blocks = 0
        # a routed block's step programs return their routing counts
        # (ops/moe.py routed_experts_apply), read with the tokens in
        # _consume_inflight: assignments by where the chosen expert
        # lives, held experts that got a row (summed over passes and
        # layers), the tiles the expert loop ran and their rows
        # (padding included), and expert-layer passes (one a chunk,
        # one a decode step of a span)
        self.moe_assignments: Dict[str, int] = {
            "held": 0, "zero": 0, "absent": 0}
        self.moe_experts_touched = 0
        self.moe_tiles = 0
        self.moe_tile_rows = 0
        self.moe_passes = 0
        # a 'retention' block's dispatches: lane-passes that read a
        # state, unfolded rows read (over lanes and passes), key blocks
        # folded and the pages handed back behind them
        self.retention_state_reads = 0
        self.retention_tail_rows = 0
        self.retention_folds = 0
        self.retention_pages_freed = 0
        # the short convolutions' dispatches: lane-passes x convolution
        # layers that read a slot's state, and the chunks that began at
        # row 0 (they read zeros, whatever the slot held)
        self.conv_state_reads = 0
        self.conv_state_resets = 0
        # a cache by layer kind: pages by kind and event — reserved at
        # admission, returned at a request's end (or its preemption) and,
        # the window kind's, released behind the window and drawn for the
        # rows ahead while the request runs
        self.kv_kind_blocks: Dict[Tuple[str, str], int] = {
            (kind, event): 0 for kind, events in (
                ("full", ("reserved", "returned")),
                ("window", ("reserved", "released", "drawn", "returned")))
            for event in events} if self._kinds else {}
        # how far the step programs' attention had to go: summed over
        # planned dispatches, the furthest lane's rows rounded up to key
        # blocks (what the key-block loop runs over), the view's whole
        # width (what a lane may hold) and the decode lanes' own rows
        # (what the paged kernel reads)
        self.view_rows_reached = 0
        self.view_rows_configured = 0
        self.view_rows_held = 0
        self.peak_blocks_in_use = 0
        self.requests_admitted = 0
        self.requests_finished = 0
        self.prefix_hit_requests = 0
        self.prefix_hit_tokens = 0  # prompt tokens whose prefill was skipped
        self.cow_copies = 0
        # sharded serving: ESTIMATED fleet-total bytes moved by the
        # collectives inside each dispatch kind (shard-shape model in
        # sharded.dispatch_collective_bytes) — stays all-zero on a
        # single-device engine, exported as
        # kubeshare_serving_collective_bytes_total
        self.collective_bytes: Dict[str, int] = {
            "prefill_chunk": 0, "decode_span": 0, "verify_span": 0}
        # eviction outcome by reason — the metrics plane's `reason`
        # label (reservation_pressure / quota_drain name the trigger
        # when evicted K/V is destroyed; tier_demote / tier_drop name
        # the tier's verdict when it is consulted instead)
        self.evictions_by_reason: Dict[str, int] = {
            "reservation_pressure": 0, "quota_drain": 0,
            "tier_demote": 0, "tier_drop": 0}
        # KV tier counters: blocks spilled host-side, blocks copied
        # back into fresh device blocks (shared rebinds AND private
        # partial-match copies), host-budget evictions, admissions that
        # recovered host-resident prefix rows, the tokens they
        # recovered, and host wall time spent staging promotions
        # (deserialize + upload enqueue — the dispatch itself overlaps
        # the pipelined step on an unguarded engine)
        self.tier_demoted_blocks = 0
        self.tier_dropped_blocks = 0
        self.tier_promoted_blocks = 0
        self.tier_hit_requests = 0
        self.tier_hit_tokens = 0
        self.tier_promotion_stall_s = 0.0
        # the remote-vs-local split of tier_hit_requests: "remote" when
        # any payload the admission consumed arrived over the fabric
        # (a peer's demotion adopted here), "local" otherwise — the
        # fleet-wide prefix bus's effectiveness signal
        self.tier_hit_requests_by_origin: Dict[str, int] = {
            "local": 0, "remote": 0}
        # wire blocks that failed their v2 crc32 on consumption — each
        # was dropped (tier miss / failed delivery) and re-prefilled,
        # never attended into a stream
        self.tier_corrupt_blocks = 0
        # chaos seam (serving/chaos.py): a FaultClock the engine
        # CONSULTS — at the top of step() (replica kill) and inside
        # _dispatch (slow/hung dispatch) — never a monkeypatch.  None
        # outside chaos runs; the fleet (or a chaos test) installs it.
        self.fault_clock = None
        self._ttft_counts = [0] * (len(TTFT_BUCKETS) + 1)  # +Inf tail
        self._ttft_sum = 0.0
        # QoS counters: preemptions by victim tenant, emitted tokens by
        # tenant, and a TTFT histogram per QoS class
        self.preemptions: Dict[str, int] = {}
        self.tenant_tokens: Dict[str, int] = {}
        self._ttft_class: Dict[str, list] = {
            cls: [[0] * (len(TTFT_BUCKETS) + 1), 0.0]
            for cls in (QOS_GUARANTEE, QOS_OPPORTUNISTIC)}
        # inter-token latency (time-between-tokens) histogram per QoS
        # class — the tail metric mixed batching exists to flatten
        self._tbt_class: Dict[str, list] = {
            cls: [[0] * (len(TBT_BUCKETS) + 1), 0.0]
            for cls in (QOS_GUARANTEE, QOS_OPPORTUNISTIC)}

        cfg = config
        top_k, top_p = ec.top_k, ec.top_p

        def pick_rows(logits, temps, keys):
            # greedy rows take the argmax; sampled rows follow the dense
            # serving split's exact order (temperature scale, then the
            # k/nucleus restriction, then categorical) so a single-slot
            # engine reproduces sample_decode_with_cache's stream
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            safe_t = jnp.where(temps > 0, temps, 1.0)
            filtered = _filter_logits(logits / safe_t[:, None], top_k, top_p)
            sampled = jax.vmap(jax.random.categorical)(keys, filtered)
            return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)

        # params ride as jit ARGUMENTS — closing over them would bake the
        # weights in as XLA constants (slow compiles, duplicated memory).
        # The prefill step serves every same-width waiting slot in ONE
        # dispatch and fuses the first-token pick (only lanes finishing
        # their prompt consume it), so a finished prefill costs no extra
        # dispatch for its first token.
        sharded = self._sharded
        sharded_prefill = sharded.prefill if sharded is not None else None
        routed = config.routed
        # every step program hands its call's host arguments over as one
        # buffer and says so here, for the launch span and the counters
        step_program = functools.partial(_step_program,
                                         carried=self._note_carried)

        def prefill(w, pk, pv, tables, starts, active, tokens, last_rows,
                    temps, keys):
            if sharded_prefill is not None:
                logits, pk, pv = sharded_prefill(
                    w, pk, pv, tables, starts, active, tokens, last_rows)
                return pick_rows(logits, temps, keys), pk, pv
            logits, pk, pv, *counts = paged_prefill_step(
                w, cfg, pk, pv, tables, starts, active, tokens, last_rows,
                routing=routed)
            return (pick_rows(logits, temps, keys), pk, pv, *counts)

        if self._diffusion:
            # whole blocks of the prompt under the block-causal mask;
            # prefill yields no token, so there is no pick and no head
            def prefill(w, pk, pv, tables, starts, active, tokens,
                        last_rows):
                return paged_diffusion_prefill(
                    w, cfg, pk, pv, tables, starts, active, tokens,
                    last_rows, routing=routed)

        if self._stateful:
            # the same programs with the states by slot (and a
            # 'retention' block's gate array) as one more donated
            # argument, the lanes' fold points and the chunk's slot after
            # it; the Recurrent comes back last, after a routed block's
            # counts
            def prefill(w, pk, pv, tables, starts, active, tokens,
                        last_rows, temps, keys, recurrent, folded, slots):
                logits, pk, pv, *rest = paged_prefill_step(
                    w, cfg, pk, pv, tables, starts, active, tokens,
                    last_rows, routing=routed, recurrent=recurrent,
                    folded=folded, slots=slots)
                return (pick_rows(logits, temps, keys), pk, pv, *rest)

        # the pool buffers are DONATED: each step updates the cache in
        # place device-side instead of materializing a second pool (on a
        # fractional-HBM pod a transient 2x cache would blow the cap)
        donated = (1, 2, 10) if self._stateful else (1, 2)
        self._prefill_step = step_program("prefill", prefill, donated)

        def diffusion(w, pk, pv, tables, lengths, active, tokens, masked,
                      open_rows, quota):
            # one pass over every lane's block: the denoising pass and
            # the commit pass are this one program (paged.py)
            return paged_diffusion_pass(
                w, cfg, pk, pv, tables, lengths, active, tokens, masked,
                open_rows, quota, routing=routed)

        def mixed_diffusion(w, pk, pv, p_table, p_start, p_tokens,
                            p_last_row, d_tables, d_lengths, d_active,
                            d_tokens, d_masked, d_open, d_quota):
            return paged_mixed_diffusion_step(
                w, cfg, pk, pv, p_table, p_start, p_tokens, p_last_row,
                d_tables, d_lengths, d_active, d_tokens, d_masked, d_open,
                d_quota, routing=routed)

        self._diffusion_step = step_program("diffusion", diffusion, (1, 2))
        self._mixed_diffusion_step = step_program(
            "mixed_diffusion", mixed_diffusion, (1, 2))

        span = ec.decode_span
        eos = ec.eos_token
        # the step over the lanes has the same shapes in the decode program
        # and in every mixed program: a model with a state by slot, whose
        # fused mixed programs are dearer to trace than the compositions
        # they replaced, traces it ONCE an engine and replays it into each
        # (inline: what a program computes is what it was; only the order
        # of the scan's closed-over operands in its text moves, which is
        # why the other models' programs are left to the letter).  A
        # function of this engine's own, so that no other engine's trace
        # (made under another test's patched module) is ever taken for it
        def decode_step(*args, **kwargs):
            return paged.paged_decode_step(*args, **kwargs)

        decode_once = jax.jit(
            decode_step, static_argnums=(1,),
            static_argnames=("routing", "grow"),
            inline=True) if self._stateful else None

        def decode(w, pk, pv, tables, lengths, active, tokens, temps,
                   keys, budgets):
            # ONE dispatch advances every lane up to `span` tokens —
            # the scan body is EXACTLY the single step (paged.py's
            # paged_decode_span, shared verbatim with the mixed step),
            # so the emitted math is span-invariant.  The sharded twin
            # keeps the same one-dispatch shape: the scan AND the
            # collectives live inside the program.
            return paged_decode_span(
                w, cfg, pick_rows, span, eos, pk, pv, tables, lengths,
                active, tokens, temps, keys, budgets, routing=routed,
                decode_step=decode_once)

        if sharded is not None:
            decode = sharded.decode_span(pick_rows, span, eos)
        if self._stateful:
            def decode(w, pk, pv, tables, lengths, active, tokens, temps,
                       keys, budgets, recurrent, folded):
                return paged_decode_span(
                    w, cfg, pick_rows, span, eos, pk, pv, tables, lengths,
                    active, tokens, temps, keys, budgets, routing=routed,
                    recurrent=recurrent, folded=folded,
                    decode_step=decode_once)

        self._decode_step = step_program("decode", decode, donated)

        def make_loop(k_units):
            # the device-resident multi-step loop: up to K span-units
            # (each the exact decode scan above) in ONE launch, with
            # on-device ring buffering and a lanes-changed early exit
            # — the host planner runs once per launch instead of once
            # per span.  K is a static arg of the fused program, so
            # each depth is its own warmed shape.
            def loop(w, pk, pv, tables, lengths, active, tokens, temps,
                     keys, budgets):
                return paged_decode_loop(
                    w, cfg, pick_rows, span, k_units, eos, pk, pv,
                    tables, lengths, active, tokens, temps, keys,
                    budgets)

            if sharded is not None:
                loop = sharded.decode_loop(pick_rows, span, k_units, eos)
            return step_program("loop", loop, (1, 2))

        # one jitted loop program per depth: just the configured K
        # normally; under autotune, EVERY power-of-two depth up to the
        # configured ceiling, so the tuner's effective-K knob only ever
        # selects among warmed shapes (K=1 is the plain decode step —
        # the loop disarmed — and needs no program here)
        loop_ks = []
        if ec.steps_per_launch > 1:
            loop_ks = ([k for k in (2 ** i for i in range(1, 32))
                        if k <= ec.steps_per_launch] if ec.autotune
                       else [ec.steps_per_launch])
        self._loop_steps = {k: make_loop(k) for k in loop_ks}

        max_order = ec.draft_ngram
        spec_w = 1 + ec.draft_len

        def make_spec_loop(k_units):
            # device residency v2: the SPECULATIVE device loop — each
            # unit drafts on device (n-gram suffix match over the
            # lane's token-history window), runs the width-W verify,
            # and applies acceptance without leaving the device; ring
            # admissions activate pre-marshaled pending lanes at span
            # boundaries.  One shape per depth, like make_loop.
            def spec_loop(w, pk, pv, tables, lengths, active, tokens,
                          temps, keys, budgets, hist, hist_len, dcaps,
                          r_tables, r_lengths, r_tokens, r_temps,
                          r_keys, r_budgets, r_hist, r_hist_len,
                          r_caps, r_count):
                return paged_spec_loop(
                    w, cfg, pick_rows, k_units, eos, max_order,
                    SPEC_LOOP_REDRAFT, spec_w, pk, pv, tables,
                    lengths, active, tokens, temps, keys, budgets,
                    hist, hist_len, dcaps, r_tables, r_lengths,
                    r_tokens, r_temps, r_keys, r_budgets, r_hist,
                    r_hist_len, r_caps, r_count)

            if sharded is not None:
                spec_loop = sharded.spec_loop(
                    pick_rows, k_units, eos, max_order,
                    SPEC_LOOP_REDRAFT, spec_w)
            return step_program("spec_loop", spec_loop, (1, 2))

        # one speculative loop program per warmed depth — exactly the
        # plain loop's depth set, armed only when speculation is on
        # and this pool runs decode plans at all
        self._spec_loops = (
            {k: make_spec_loop(k) for k in loop_ks}
            if ec.speculative and ec.pool_role != "prefill" else {})

        def mixed(w, pk, pv, p_table, p_start, p_tokens, p_last_row,
                  p_temp, p_key, d_tables, d_lengths, d_active,
                  d_tokens, d_temps, d_keys, d_budgets):
            # the stall-free fused dispatch: one bounded prefill chunk
            # + the full decode span, ONE program, the chunk riding the
            # span's first pass over the weights; a row's math (and
            # therefore the emitted streams) is the entry points' above.
            # Compiles one shape per prefill bucket width (warmed).
            return paged_mixed_step(
                w, cfg, pick_rows, span, eos, pk, pv, p_table, p_start,
                p_tokens, p_last_row, p_temp, p_key, d_tables,
                d_lengths, d_active, d_tokens, d_temps, d_keys,
                d_budgets, routing=routed, decode_step=decode_once)

        if sharded is not None:
            mixed = sharded.mixed_step(pick_rows, span, eos)
        if self._stateful:
            def mixed(w, pk, pv, p_table, p_start, p_tokens, p_last_row,
                      p_temp, p_key, d_tables, d_lengths, d_active,
                      d_tokens, d_temps, d_keys, d_budgets, recurrent,
                      p_folded, p_slot, d_folded):
                return paged_mixed_step(
                    w, cfg, pick_rows, span, eos, pk, pv, p_table, p_start,
                    p_tokens, p_last_row, p_temp, p_key, d_tables,
                    d_lengths, d_active, d_tokens, d_temps, d_keys,
                    d_budgets, routing=routed, recurrent=recurrent,
                    p_folded=p_folded, p_slot=p_slot, d_folded=d_folded,
                    decode_step=decode_once)

        self._mixed_step = step_program(
            "mixed", mixed, (1, 2, 16) if self._stateful else (1, 2))

        def verify(w, pk, pv, tables, lengths, active, tokens, widths,
                   temps, keys):
            # the draft-verify chunk: every lane's self-drafted tokens
            # scored in ONE width-W dispatch, each column picked under
            # its own emission's temperature/PRNG key — acceptance
            # reproduces the sequential stream exactly (bit-exact with
            # speculation off by construction).
            return paged_verify_span(
                w, cfg, pick_rows, pk, pv, tables, lengths, active,
                tokens, widths, temps, keys)

        if sharded is not None:
            verify = sharded.verify_span(pick_rows)
        self._verify_step = step_program("verify", verify, (1, 2))

        def mixed_verify(w, pk, pv, p_table, p_start, p_tokens,
                         p_last_row, p_temp, p_key, d_tables, d_lengths,
                         d_active, d_tokens, d_widths, d_temps, d_keys):
            # the speculative fused dispatch: one bounded prefill chunk
            # + the verify chunk, one program — same composition-over-
            # disjoint-blocks argument as the plain mixed step, so both
            # sides' streams are unchanged.
            return paged_mixed_verify_step(
                w, cfg, pick_rows, pk, pv, p_table, p_start, p_tokens,
                p_last_row, p_temp, p_key, d_tables, d_lengths,
                d_active, d_tokens, d_widths, d_temps, d_keys)

        if sharded is not None:
            mixed_verify = sharded.mixed_verify_step(pick_rows)
        self._mixed_verify_step = step_program(
            "mixed_verify", mixed_verify, (1, 2))
        # the copy-on-write primitive: one block, all layers, K and V —
        # a single static shape, so the cache adds exactly ONE compile.
        # Wrapped per-engine (like prefill/decode above): jitting the
        # module-level function directly would share one jit cache
        # across engines with different pool shapes.
        def copy(pk, pv, src, dst):
            return paged_copy_block(pk, pv, src, dst)

        if sharded is not None:
            copy = sharded.copy_block
        self._copy_step = _block_program("copy", copy, (0, 1))

        # the KV tier's promotion primitive: one block's host payload
        # into a fresh pool block — like the CoW copy, a single static
        # shape (dst traced, slab shape fixed), warmed when the tier is
        # enabled so promotion never compiles mid-serve.
        def upload(pk, pv, dst, k_slab, v_slab):
            return paged_upload_block(pk, pv, dst, k_slab, v_slab)

        if sharded is not None:
            # the sharded twin re-scatters the host-shaped slab over the
            # pool's head sharding, so tier promotion and migration
            # unpack are sharding-agnostic host-side
            upload = sharded.upload_block
        self._upload_step = _block_program("upload", upload, (0, 1))

        # the online autotuner (serving/autotune.py): ticked by step()
        # between consume and plan, so _plan_step always reads
        # freshly-retuned knobs.  The policy is pluggable and
        # sandboxed — only values inside the warmed-shape envelope
        # above ever apply.
        self._tuner = (AutoTuner.for_engine(
            self, policy=tuning_policy or AnalyticPolicy(),
            interval=ec.autotune_interval)
            if ec.autotune else None)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def _lifetime_rows(self, prompt_len: int, max_new: int,
                       cover: int) -> int:
        """Cache rows a request occupies over its life in THIS pool: a
        prefill-role pool only ever writes the prompt's K/V (decode
        rows land in the decode pool after migration), so it reserves
        just the chunk-plan cover — the HBM saving that makes a small
        prefill cell viable.  Everywhere else: the full lifetime.  The
        max_request_len feasibility check stays on FULL rows (submit) —
        a request the decode pool can never hold must fail loudly up
        front."""
        if self.engine_config.pool_role == "prefill":
            return cover
        rows = max(cover, self._request_rows(prompt_len, max_new))
        # a 'retention' lane's pages do not grow with its length: a fold
        # frees the pages behind it, so a request is funded by its tail
        return min(rows, self._tail_rows) if self._retention else rows

    def _request_rows(self, prompt_len: int, max_new: int) -> int:
        """Rows a request's prompt and generation reach: under
        generation by diffusion over blocks the last block is written
        whole, so up to the next multiple of the block length."""
        rows, b = prompt_len + max_new, self._diffusion
        return -(-rows // b) * b if b else rows

    def _prefill_plan(self, prompt_len: int, start: int = 0):
        """:func:`plan_prefill_chunks` of a prompt from ``start`` on.  A
        diffusion configuration prefills the prompt's whole blocks only
        (its last ``len % B`` tokens sit, already known, in the first
        generated block) and needs no logits of them: a prompt shorter
        than a block, or one the prefix cache covers, plans nothing."""
        ec, b = self.engine_config, self._diffusion
        if self._stateful:
            # chunks from row 0 on, none sliding back over rows a fold may
            # have taken (or a convolution's state has moved past): the
            # last is padded FORWARD to its bucket, and the padding's rows
            # are written nowhere (paged._prefill_rows)
            chunk, floor = ec.prefill_chunk, min(self._warmed_widths)
            plan = [(s, chunk, chunk - 1)
                    for s in range(0, prompt_len - chunk + 1, chunk)]
            rest = prompt_len % chunk
            if rest:
                plan.append((prompt_len - rest,
                             max(bucket_width(rest, chunk), floor),
                             rest - 1))
            return plan, prompt_len
        rows = prompt_len // b * b if b else prompt_len
        if b and rows <= start:
            return [], rows
        return plan_prefill_chunks(rows, ec.prefill_chunk,
                                   ec.max_request_len, start)

    def submit(self, request: Request) -> RequestResult:
        """Queue a request; validation failures raise HERE (loudly), a
        merely-busy pool queues."""
        prompt = np.asarray(request.prompt, np.int32)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError(f"prompt must be a non-empty 1-D token array, "
                             f"got shape {prompt.shape}")
        if request.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {request.max_new_tokens}")
        if request.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {request.temperature}")
        if request.temperature > 0.0 and request.rng is None:
            raise ValueError("sampled requests (temperature > 0) must carry rng")
        if request.temperature > 0.0 and self._diffusion:
            raise ValueError(
                "a configuration that generates by diffusion over blocks "
                "serves greedy requests only (temperature 0): its passes "
                "commit the argmax by its confidence")
        if request.rid in self._results and not self._results[request.rid].done:
            raise ValueError(f"request id {request.rid!r} already in flight")
        try:
            spec = self.tenants.get(request.tenant)
        except KeyError as exc:
            raise ValueError(str(exc)) from None
        ec = self.engine_config
        if ec.pool_role == "decode":
            raise RuntimeError(
                "a decode-role pool admits only through admit_migrated() "
                "— submit to the DisaggRouter (or the prefill pool)")
        plan, cover = self._prefill_plan(prompt.size)
        total_rows = max(cover, self._request_rows(
            prompt.size, request.max_new_tokens))
        if total_rows > ec.max_request_len:
            raise ValueError(
                f"request {request.rid!r}: prompt {prompt.size} + "
                f"max_new_tokens {request.max_new_tokens} needs "
                f"{total_rows} cache rows, over max_request_len "
                f"{ec.max_request_len}"
            )
        needed = self.allocator.blocks_for_tokens(
            self._lifetime_rows(prompt.size, request.max_new_tokens, cover))
        if needed > self.allocator.num_blocks - 1:
            raise BlockExhausted(
                f"request {request.rid!r} needs {needed} blocks but the "
                f"pool only has {self.allocator.num_blocks - 1} — it can "
                f"NEVER be admitted (grow num_blocks or shrink the request)"
            )
        if self._kinds and min(needed, self._window_pages) \
                > self.window_allocator.num_blocks - 1:
            raise BlockExhausted(
                f"request {request.rid!r} needs "
                f"{min(needed, self._window_pages)} blocks of the window "
                f"kind but its pool only has "
                f"{self.window_allocator.num_blocks - 1} — it can NEVER be "
                f"admitted (grow num_blocks or shrink the request)")
        if spec.kv_block_quota is not None and needed > spec.kv_block_quota:
            raise QuotaExceeded(
                f"request {request.rid!r} needs {needed} blocks but "
                f"tenant {spec.name!r}'s quota is {spec.kv_block_quota} "
                f"— it can NEVER be admitted (raise the quota or shrink "
                f"the request)"
            )
        result = RequestResult(rid=request.rid, prompt_len=prompt.size,
                               submitted_at=time.monotonic())
        self._results[request.rid] = result
        # the plan and block count ride with the queued request — _admit
        # must not redo this work on every scheduling tick
        self._queue.push(request.tenant, _Pending(
            rid=request.rid, tenant=request.tenant, prompt=prompt,
            max_new=request.max_new_tokens,
            temperature=request.temperature, plan=plan, needed=needed,
            rng=request.rng))
        return result

    def admit_migrated(
        self, *,
        rid: str,
        tenant: str,
        prompt: np.ndarray,
        first_token: int,
        max_new: int,
        temperature: float,
        step_keys: np.ndarray,
        payloads: List[bytes],
        result: RequestResult,
        emitted_prefix: List[int],
        last_token_at: Optional[float],
        hint: Optional[List[int]] = None,
    ) -> bool:
        """Admit a request that finished prefill in ANOTHER pool: the
        disagg router's decode-side entry point.  Reserves the full
        decode lifetime's blocks, uploads each migrated wire frame
        through the warmed ``paged_upload_block`` shape (pipelined —
        guard-only sync, so unpacks overlap the in-flight decode
        dispatch), and builds a slot indistinguishable from one that
        just passed :meth:`_finish_prefill` here: ``length`` is the
        prompt, ``generated`` is the first (prefill-pool-picked) token,
        the key schedule continues at ``step_keys[0]``, the drafter's
        window is ``prompt + [first_token]`` with the prefill-side
        trie hint carried over — so every later emission is bit-exact
        with the monolithic engine by construction.

        Returns False (reserving nothing) when no slot is free or the
        reservation cannot be funded — the router keeps the ticket
        pending and retries after this pool's next step (or preempts).
        """
        ec = self.engine_config
        if ec.pool_role == "prefill":
            raise RuntimeError(
                "a prefill-role pool cannot admit migrated requests")
        spec = self.tenants.get(tenant)
        slot = next((s for s in self._slots if s.state == "free"), None)
        if slot is None:
            return False
        prompt = np.asarray(prompt, np.int32)
        needed = self.allocator.blocks_for_tokens(prompt.size + max_new)
        if len(payloads) > needed:
            raise ValueError(
                f"migrated chain has {len(payloads)} blocks but the "
                f"decode lifetime only spans {needed}")
        # crc-validate every migrated frame BEFORE reserving or
        # uploading anything: a corrupt chain must fail delivery with
        # zero state mutated here — the migrator turns the raise into
        # a failed delivery and the router's TTL path re-queues the
        # request to prefill-from-cache
        try:
            frames = [unpack_block(p) for p in payloads]
        except WireCorruption:
            self.tier_corrupt_blocks += 1
            raise
        evict_first = (set(self.tenants.opportunistic())
                       if spec.is_guarantee else None)
        try:
            blocks = self.allocator.reserve(
                needed, rid, tenant=spec.name,
                quota=spec.kv_block_quota,
                evict_tenants_first=evict_first)
        except (BlockExhausted, QuotaExceeded):
            return False
        for (_, k_slab, v_slab), dst in zip(frames, blocks):
            pk, pv = self._dispatch(
                self._upload_step, self.pool.k, self.pool.v,
                jnp.asarray(dst, jnp.int32),
                jnp.asarray(k_slab), jnp.asarray(v_slab))
            self.pool = replace(self.pool, k=pk, v=pv)
        slot.state = "decode"
        slot.rid = rid
        slot.tenant = spec.name
        slot.blocks = list(blocks)
        slot.table[:] = 0
        slot.table[: len(blocks)] = blocks
        slot.length = prompt.size
        slot.generated = [int(first_token)]
        slot.emitted_prefix = list(emitted_prefix)
        slot.last_token_at = last_token_at
        slot.prompt = prompt
        slot.plan = []
        slot.max_new = max_new
        slot.temperature = temperature
        slot.first_key = np.zeros((2,), np.uint32)  # consumed upstream
        slot.step_keys = np.asarray(step_keys, np.uint32).reshape(-1, 2)
        slot.result = result
        self._results[rid] = result
        if ec.speculative:
            slot.drafter = NGramDrafter(ec.draft_ngram, prompt)
            if hint:
                slot.drafter.hint(hint)
            slot.drafter.extend([int(first_token)])
            slot.draft_width = min(ec.draft_len, self._draft_width_cap)
            slot.accept_rate = 0.5
        self.peak_blocks_in_use = max(
            self.peak_blocks_in_use, self.allocator.blocks_in_use)
        return True

    def step(self) -> bool:
        """One scheduling iteration: admit what fits, consume the
        previous dispatch's results, PLAN the next step
        (:meth:`_plan_step` — which lanes prefill / decode / verify,
        at what widths), then dispatch the plan
        (:meth:`_dispatch_plan` — device arguments and launch only).

        Pipelining: admission (pure host work — queue, allocator,
        trie) runs BEFORE the previous dispatch's results are read, so
        on an unguarded engine it overlaps device execution; the
        emitted tokens are then consumed (planning needs fresh lane
        state — the drafter reads ``generated``) and the next step
        dispatched.  Returns False when the engine is fully idle.

        Every phase is a ``kubeshare.engine.<phase>`` span
        (utils/profiling.py) whose seconds feed ``host_seconds``
        (exported as ``kubeshare_serving_host_seconds_total{phase}``)
        — the raw material for proving, not asserting, that the
        device-resident loop removes host overhead from the decode hot
        path; under a profiler session the same spans are in the trace
        on the device's clock."""
        if self.fault_clock is not None:
            # chaos seam: a planned replica kill raises ReplicaKilled
            # HERE, before any host state mutates this step — the
            # crashed engine's host-side records stay consistent for
            # the fleet's recovery walk
            self.fault_clock.on_engine_step(self)
        hs = self.host_seconds
        with profiling.span("kubeshare.engine.admit",
                            queued=len(self._queue)) as phase:
            before = (self.requests_admitted, self.prefix_hit_tokens)
            self._admit()
            # what the call did, beside how long it took: a longer
            # admit with nothing admitted is the queue's walk, one
            # with matched rows the trie's
            phase.set(admitted=self.requests_admitted - before[0],
                      matched_rows=self.prefix_hit_tokens - before[1])
        hs["admit"] += phase.seconds
        with profiling.span("kubeshare.engine.consume") as phase:
            consumed = self._consume_inflight()
        hs["consume"] += phase.seconds
        # the tuner ticks BETWEEN consume and plan: it reads the
        # fully-consumed counters and retunes its knobs before
        # _plan_step consults them — and its wall time lands in the
        # "tune" phase, never in "plan" (tuner overhead is
        # first-class observable, and the planner/host counters
        # exclude it; no tuner: the phase stays exactly zero)
        if self._tuner is not None:
            with profiling.span("kubeshare.engine.tune") as phase:
                self._tuner.tick()
            hs["tune"] += phase.seconds
        with profiling.span("kubeshare.engine.plan") as phase:
            plan = self._plan_step()
        hs["plan"] += phase.seconds
        if plan is None:
            return consumed
        with profiling.span("kubeshare.engine.dispatch") as phase:
            self._dispatch_plan(plan)
        hs["dispatch"] += phase.seconds
        return True

    def _plan_step(self) -> Optional[_StepPlan]:
        """The scheduling decision, free of dispatch mechanics (the
        first slice of the scheduler/dispatch split): pick this step's
        work and its widths, returning a :class:`_StepPlan` (None =
        nothing to do).

        Discipline: when prefill and decode work coexist (and
        ``mixed`` is on, the default) ONE fused dispatch advances
        every decode lane AND consumes one budget-bounded prefill
        chunk — decode lanes never wait behind a prompt.  With
        ``mixed`` off, prefill has strict priority (the Orca either/or
        discipline — TTFT-optimal, but every prompt chunk stalls every
        decode lane for its full duration).  Either way, filling slots
        rotate round-robin so a many-chunk prompt cannot monopolize
        prefill ticks.  The decode phase itself has two variants
        (:meth:`_plan_decode_phase`): the plain span, or — speculative
        mode, when any lane drafted — one verify chunk, or — with
        ``steps_per_launch > 1`` and a pure-decode step — the
        device-resident multi-step loop."""
        self.host_planner_invocations += 1
        prefill = [s for s in self._slots if s.state == "prefill"]
        decode = [s for s in self._slots if s.state == "decode"]
        ec = self.engine_config
        if prefill and decode and ec.mixed:
            slot = self._next_prefill_slot(prefill)
            chunk = self._sliced_chunk(slot)
            if chunk[1] > self._mixed_budget:
                # an unsliceable pad-forward tail over the budget (its
                # logits row sits inside the chunk): the one shape that
                # still stalls decode, for a single bounded dispatch
                return _StepPlan("prefill", prefill_slot=slot,
                                 chunk=chunk)
            plan = self._plan_decode_phase(decode, fused=True)
            plan.kind = {"verify": "mixed_verify",
                         "diffusion": "mixed_diffusion"}.get(plan.kind,
                                                             "mixed")
            plan.prefill_slot, plan.chunk = slot, chunk
            return plan
        if prefill:
            slot = self._next_prefill_slot(prefill)
            return _StepPlan("prefill", prefill_slot=slot,
                             chunk=slot.plan.pop(0))
        if decode:
            return self._plan_decode_phase(decode)
        return None

    def _plan_decode_phase(self, decode: List[_Slot],
                           fused: bool = False) -> _StepPlan:
        """Decode-phase variant selection.  Speculative mode: lanes
        whose drafter found a continuation ride ONE verify chunk;
        lanes without a draft ride along at width 1 (for them the
        chunk IS a decode step — one pick, one emission).  When nobody
        drafted, the plain decode span is strictly better (it emits up
        to ``decode_span`` per dispatch), so the plan falls back to
        it.

        The device loop (``steps_per_launch > 1``) fires on any
        NON-fused decode-phase step (a mixed step carries per-chunk
        prefill host work and cannot run headless for K units).  A
        DRAFTED round rides the SPECULATIVE loop (device residency
        v2): the host draft is only the arming signal — some lane has
        a continuation worth verifying — and the device re-drafts
        every unit, the first included, from its own on-device history
        window, so draft CONTENT stays scheduling-only and streams
        stay bit-exact (verification is exact-match against the
        engine's own picks, so every draft schedule emits the
        identical tokens).  A no-draft round rides the plain decode
        loop.  The launch ENVELOPE is this plan: which lanes, span
        width, and up to K units; the dispatcher runs the fused
        program and the device decides how many units actually
        execute."""
        ec = self.engine_config
        if self._diffusion:
            # one pass over every lane's block, whatever each is at
            return _StepPlan("diffusion", decode_slots=decode)
        if ec.speculative:
            drafts = self._plan_drafts(decode)
            if drafts:
                if self._loop_k > 1 and not fused and self._spec_loops:
                    return _StepPlan("spec_loop", decode_slots=decode,
                                     drafts=drafts)
                width = 1 + _pow2_ceil(
                    max(len(d) for d in drafts.values()))
                return _StepPlan("verify", decode_slots=decode,
                                 drafts=drafts, verify_width=width)
        if self._loop_k > 1 and not fused:
            return _StepPlan("loop", decode_slots=decode)
        return _StepPlan("decode", decode_slots=decode)

    def _plan_drafts(self, decode: List[_Slot]) -> Dict[int, List[int]]:
        """Each decode lane's proposal for this step, truncated to
        ``min(adaptive width, remaining budget - 1)`` — a verify round
        emits at most k + 1 tokens (accepted prefix + correction
        pick), so a draft wider than remaining - 1 could only write
        dead K/V rows past what the request may emit."""
        drafts: Dict[int, List[int]] = {}
        for slot in decode:
            rem = slot.max_new - len(slot.generated)
            k = min(slot.draft_width, rem - 1)
            if k < 1:
                continue
            prop = slot.drafter.propose(k)
            if prop:
                drafts[slot.idx] = prop
        return drafts

    def _dispatch_plan(self, plan: _StepPlan) -> None:
        """Launch one planned step — device-argument marshaling and
        dispatch only; every scheduling decision was made in
        :meth:`_plan_step`."""
        self._begin_launch(plan)
        # the fleet watchdog's hang budget scales by the units this
        # launch may legitimately cover — a deep loop is slower than a
        # span WITHOUT being hung
        self.last_launch_units = (self._loop_k
                                  if plan.kind in ("loop", "spec_loop")
                                  else 1)
        if plan.kind == "mixed":
            self._run_mixed_step(plan.decode_slots, plan.prefill_slot,
                                 plan.chunk)
        elif plan.kind == "mixed_verify":
            self._run_mixed_verify_step(plan)
        elif plan.kind == "prefill":
            self._run_prefill_chunk(plan.prefill_slot, plan.chunk)
        elif plan.kind == "verify":
            self._run_verify_step(plan)
        elif plan.kind == "spec_loop":
            self._run_spec_loop_step(plan)
        elif plan.kind == "loop":
            self._run_loop_step(plan.decode_slots)
        elif plan.kind in ("diffusion", "mixed_diffusion"):
            self._run_diffusion_step(plan)
        else:
            self._run_decode_step(plan.decode_slots)

    def run(self) -> Dict[str, RequestResult]:
        """Drain the queue and every in-flight slot; returns results by
        request id."""
        try:
            while self.step():
                pass
        finally:
            if self.guard is not None:
                self.guard.finish()
        return dict(self._results)

    @property
    def idle(self) -> bool:
        return (not self._queue and self._inflight is None
                and not self._ring_staged
                and all(s.state == "free" for s in self._slots))

    def result(self, rid: str) -> RequestResult:
        return self._results[rid]

    def pop_finished(self) -> Dict[str, RequestResult]:
        """Remove and return every completed result — the live-loop
        caller's eviction point.  A server driving :meth:`step` forever
        must drain results here, or the result map (each with its full
        token list) grows with every request ever served; the
        :meth:`run` drain pattern reads its returned snapshot instead."""
        done = {rid: r for rid, r in self._results.items() if r.done}
        for rid in done:
            del self._results[rid]
        return done

    # ------------------------------------------------------------------
    # fleet routing probes (serving/fleet.py) — both read-only, called
    # against every replica per arrival, so neither may mutate engine
    # state or touch the device.
    def prefix_match_len(self, tokens) -> int:
        """Tokens of ``tokens`` this engine's radix trie covers (device
        or host tier) — 0 when prefix caching is off."""
        if self.prefix_index is None:
            return 0
        return self.prefix_index.match_len(tokens)

    def load_probe(self) -> Dict[str, int]:
        """Cheap load snapshot for routing tie-breaks and spill
        decisions: queue depth, free slots, and allocatable blocks
        (free + cached-idle, since the allocator evicts cached blocks
        on demand)."""
        return {
            "queue_depth": len(self._queue),
            "free_slots": sum(1 for s in self._slots
                              if s.state == "free"),
            "free_blocks": (self.allocator.free_blocks
                            + self.allocator.cached_idle_blocks),
        }

    def _verify_ks(self) -> List[int]:
        """Every draft width the adaptive controller can reach: powers
        of two from 1 up to ``draft_len`` (the verify dispatch is then
        width ``1 + k``)."""
        ks, k = [], 1
        while k <= self.engine_config.draft_len:
            ks.append(k)
            k *= 2
        return ks

    def warmup(self) -> None:
        """Compile every step the engine can ever dispatch: the decode
        step, one prefill chunk per bucketed width, and (mixed
        batching on) one MIXED shape per bucketed width — a sliced
        fused chunk is always a power-of-two piece at or under the
        budget, so the same bucket set covers it.  Speculative mode
        adds one VERIFY shape per reachable draft width (and the fused
        mixed-verify cross product).  After this, a workload of any
        shape runs with ZERO recompilation (compile_counts stays fixed
        — test-asserted).  Every program warmed is registered under its
        name in ``serving/stages.py`` (:meth:`_warm`), from which a
        trace's reader builds its table of stages; nothing is lowered
        for that here."""
        ec = self.engine_config
        # the bucket universe is computed once in __init__ (shared with
        # the autotuner's fused-budget envelope): the configured chunk
        # plus smaller powers of two, capped at the slot row bound;
        # empty on a decode-role pool
        if self._diffusion:
            return self._warmup_diffusion()
        widths = self._warmed_widths
        s = ec.num_slots
        one = np.zeros((1,), np.int32)
        zeros_s = np.zeros((s,), np.int32)
        # a 'retention' engine's last arguments (:meth:`_recurrent_args`):
        # an all-inactive call folds nothing and leaves every state alone
        def recurrent():
            return ((Recurrent(self.pool.gate, self.states),)
                    if self._stateful else ())

        p_fold = (one, one) if self._stateful else ()
        d_fold = (zeros_s,) if self._stateful else ()
        for width in sorted(widths):
            # the pool rides through every warmup call (its buffers are
            # donated); the only writes land in the scratch block
            _, pk, pv, *rest = self._warm(
                "prefill", (width,), self._prefill_step,
                self.params, self.pool.k, self.pool.v,
                np.zeros((1, self._table_entries), np.int32),
                one, np.zeros((1,), bool),
                np.zeros((1, width), np.int32), one,
                np.zeros((1,), np.float32),
                np.zeros((1, 2), np.uint32), *recurrent(), *p_fold)
            self._keep_cache(pk, pv, rest)
            # mixed shapes only for widths that can actually ride
            # fused: step() routes any chunk wider than the budget to
            # the standalone path, so warming those would burn the most
            # expensive compiles on unreachable shapes.  Under autotune
            # EVERY width warms — the tuned budget may move up to any
            # warmed bucket, and a budget change must never compile
            if ec.mixed and (ec.autotune or width <= self._mixed_budget):
                _, _, pk, pv, *rest = self._warm(
                    "mixed", (width,), self._mixed_step,
                    self.params, self.pool.k, self.pool.v,
                    np.zeros((1, self._table_entries), np.int32), one,
                    np.zeros((1, width), np.int32), one,
                    np.zeros((1,), np.float32),
                    np.zeros((1, 2), np.uint32),
                    np.zeros((s, self._table_entries), np.int32),
                    zeros_s, np.zeros((s,), bool), zeros_s,
                    np.zeros((s,), np.float32),
                    np.zeros((s, ec.decode_span, 2), np.uint32),
                    zeros_s, *recurrent(), *p_fold, *d_fold)
                self._keep_cache(pk, pv, rest)
                if ec.speculative:
                    # every (prefill bucket) x (verify width) fused
                    # shape the speculative scheduler can reach
                    for k in self._verify_ks():
                        _, _, _, pk, pv = self._warm(
                            "mixed_verify", (width, 1 + k),
                            self._mixed_verify_step,
                            self.params, self.pool.k, self.pool.v,
                            np.zeros((1, self._table_entries), np.int32),
                            one, np.zeros((1, width), np.int32), one,
                            np.zeros((1,), np.float32),
                            np.zeros((1, 2), np.uint32),
                            np.zeros((s, self._table_entries), np.int32),
                            zeros_s, np.zeros((s,), bool),
                            np.full((s, 1 + k), -1, np.int32),
                            np.ones((s,), np.int32),
                            np.zeros((s,), np.float32),
                            np.zeros((s, 1 + k, 2), np.uint32))
                        self.pool = replace(self.pool, k=pk, v=pv)
        if ec.pool_role != "prefill":
            _, pk, pv, *rest = self._warm(
                "decode", (), self._decode_step,
                self.params, self.pool.k, self.pool.v,
                np.zeros((s, self._table_entries), np.int32),
                zeros_s, np.zeros((s,), bool), zeros_s,
                np.zeros((s,), np.float32),
                np.zeros((s, ec.decode_span, 2), np.uint32), zeros_s,
                *recurrent(), *d_fold)
            self._keep_cache(pk, pv, rest)
        for k_depth, loop_step in sorted(self._loop_steps.items()):
            # one shape per warmed loop depth (K is baked in; lane
            # masks, budgets, and the units-ran count are all
            # dynamic).  The all-inactive warmup call exits at unit 0
            # — the loop cond checks any(alive) precisely so each
            # depth costs one compile and zero scratch-block work.
            _, _, pk, pv = self._warm(
                "loop", (k_depth,), loop_step,
                self.params, self.pool.k, self.pool.v,
                np.zeros((s, self._table_entries), np.int32),
                zeros_s, np.zeros((s,), bool), zeros_s,
                np.zeros((s,), np.float32),
                np.zeros((s, k_depth * ec.decode_span, 2), np.uint32),
                zeros_s)
            self.pool = replace(self.pool, k=pk, v=pv)
        for k_depth, spec_step in sorted(self._spec_loops.items()):
            # the speculative loop's one shape per depth: all-inactive
            # lanes exit at unit 0 exactly like the plain loop, and a
            # ring count of 0 keeps the admit path dead.  The ring
            # arrays' row count is the CONFIGURED admission_ring — a
            # static part of the shape, zero rows when the ring is off.
            w = 1 + ec.draft_len
            r = ec.admission_ring
            _, _, _, _, _, pk, pv = self._warm(
                "spec_loop", (k_depth,), spec_step,
                self.params, self.pool.k, self.pool.v,
                np.zeros((s, self._table_entries), np.int32),
                zeros_s, np.zeros((s,), bool), zeros_s,
                np.zeros((s,), np.float32),
                np.zeros((s, k_depth * w, 2), np.uint32),
                zeros_s, np.zeros((s, SPEC_LOOP_HIST), np.int32),
                zeros_s, zeros_s,
                np.zeros((r, self._table_width), np.int32),
                np.zeros((r,), np.int32),
                np.zeros((r,), np.int32),
                np.zeros((r,), np.float32),
                np.zeros((r, k_depth * w, 2), np.uint32),
                np.zeros((r,), np.int32),
                np.zeros((r, SPEC_LOOP_HIST), np.int32),
                np.zeros((r,), np.int32),
                np.zeros((r,), np.int32),
                np.zeros((), np.int32))
            self.pool = replace(self.pool, k=pk, v=pv)
        if ec.speculative and ec.pool_role != "prefill":
            # verify widths are 1 + pow2(max draft) with the adaptive
            # controller confined to power-of-two widths <= draft_len,
            # so this small set is exhaustive
            for k in self._verify_ks():
                _, _, pk, pv = self._warm(
                    "verify", (1 + k,), self._verify_step,
                    self.params, self.pool.k, self.pool.v,
                    np.zeros((s, self._table_entries), np.int32),
                    zeros_s, np.zeros((s,), bool),
                    np.full((s, 1 + k), -1, np.int32),
                    np.ones((s,), np.int32),
                    np.zeros((s,), np.float32),
                    np.zeros((s, 1 + k, 2), np.uint32))
                self.pool = replace(self.pool, k=pk, v=pv)
        if self.prefix_index is not None and ec.pool_role != "decode":
            # the CoW copy's one shape; scratch -> scratch is a no-op
            # (a decode-role pool never admits through the prefix
            # matcher, so divergence copies cannot occur there)
            zero = jnp.zeros((), jnp.int32)
            pk, pv = self._warm("copy", (), self._copy_step,
                                self.pool.k, self.pool.v, zero, zero)
            self.pool = replace(self.pool, k=pk, v=pv)
        if self.host_tier is not None or ec.pool_role == "decode":
            # the ONE upload shape tier promotions AND migration
            # unpacks share (a decode pool needs it even with tiering
            # off): a zero slab into the scratch block (whose rows are
            # dead by construction)
            cfg2 = self.model_config
            k_slab, v_slab = (
                jnp.zeros(shape, cfg2.dtype) for shape in
                kv_row_layout(cfg2).block_shapes(ec.block_size))
            pk, pv = self._warm(
                "upload", (), self._upload_step,
                self.pool.k, self.pool.v, jnp.zeros((), jnp.int32),
                k_slab, v_slab)
            self.pool = replace(self.pool, k=pk, v=pv)
        jax.block_until_ready(self.pool.k)

    def _warm(self, kind: str, widths: Tuple[int, ...], fn, *args):
        """One warm-up call of the step program ``fn``, registered as
        ``<kind>/<width>`` (the name :meth:`_launch` gives the same
        program's launches) with its arguments' shapes."""
        stages.register(stages.program_name(kind, *widths), fn, args)
        return fn(*args)

    def _warmup_diffusion(self) -> None:
        """:meth:`warmup` of a configuration that generates by diffusion
        over blocks: one prefill chunk and (mixed batching on) one mixed
        shape per bucketed width of whole blocks, the pass over the
        lanes' blocks alone, and the copy-on-write's one shape.  Every
        write lands in the scratch block."""
        ec = self.engine_config
        s, b = ec.num_slots, self._diffusion
        one = np.zeros((1,), np.int32)
        table = np.zeros((1, self._table_entries), np.int32)
        lanes = (np.zeros((s, self._table_entries), np.int32),
                 np.zeros((s,), np.int32), np.zeros((s,), bool),
                 np.zeros((s, b), np.int32), np.zeros((s, b), bool),
                 np.zeros((s, b), bool), np.zeros((s,), np.int32))
        for width in sorted(self._warmed_widths):
            tokens = np.zeros((1, width), np.int32)
            pk, pv, *_ = self._warm(
                "prefill", (width,), self._prefill_step,
                self.params, self.pool.k, self.pool.v, table, one,
                np.zeros((1,), bool), tokens, one)
            self.pool = replace(self.pool, k=pk, v=pv)
            if ec.mixed and width <= self._mixed_budget:
                _, _, pk, pv, *_ = self._warm(
                    "mixed_diffusion", (width,), self._mixed_diffusion_step,
                    self.params, self.pool.k, self.pool.v, table, one,
                    tokens, one, *lanes)
                self.pool = replace(self.pool, k=pk, v=pv)
        _, _, pk, pv, *_ = self._warm(
            "diffusion", (), self._diffusion_step,
            self.params, self.pool.k, self.pool.v, *lanes)
        self.pool = replace(self.pool, k=pk, v=pv)
        if self.prefix_index is not None:
            zero = jnp.zeros((), jnp.int32)
            pk, pv = self._warm("copy", (), self._copy_step,
                                self.pool.k, self.pool.v, zero, zero)
            self.pool = replace(self.pool, k=pk, v=pv)
        jax.block_until_ready(self.pool.k)

    def compile_counts(self) -> Dict[str, int]:
        """Jit cache sizes per step function — the zero-recompile
        assertion's raw data."""
        return {
            "decode": self._decode_step._cache_size(),
            "prefill": self._prefill_step._cache_size(),
            "mixed": self._mixed_step._cache_size(),
            "verify": self._verify_step._cache_size(),
            "mixed_verify": self._mixed_verify_step._cache_size(),
            "diffusion": self._diffusion_step._cache_size(),
            "mixed_diffusion": self._mixed_diffusion_step._cache_size(),
            "copy": self._copy_step._cache_size(),
            "upload": self._upload_step._cache_size(),
            "loop": sum(step._cache_size()
                        for step in self._loop_steps.values()),
            "spec_loop": sum(step._cache_size()
                             for step in self._spec_loops.values()),
        }

    # ------------------------------------------------------------------
    # metrics (the collector-plane scrape surface)
    # ------------------------------------------------------------------
    def collect_metrics(self) -> List[MetricFamily]:
        """Serving-plane runtime metrics in the same exposition format
        the token daemons and the chip collector speak
        (``utils/promtext``) — a stock Prometheus scrapes the serving
        pod exactly like it scrapes ``gpu_capacity``."""
        req = MetricFamily(
            "kubeshare_serving_requests_total",
            "Requests by lifecycle stage.", "counter")
        req.add({"stage": "admitted"}, self.requests_admitted)
        req.add({"stage": "finished"}, self.requests_finished)
        blocks = MetricFamily(
            "kubeshare_serving_kv_blocks",
            "KV pool blocks by state (in_use counts refcounted blocks; "
            "cached are idle prefix-cache blocks, evictable on demand).",
            "gauge")
        blocks.add({"state": "in_use"}, self.allocator.blocks_in_use)
        blocks.add({"state": "free"}, self.allocator.free_blocks)
        blocks.add({"state": "cached"}, self.allocator.cached_idle_blocks)
        tokens = MetricFamily(
            "kubeshare_serving_tokens_generated_total",
            "Tokens emitted across all requests.", "counter")
        tokens.add({}, self.tokens_generated)
        # disaggregated pools tag their latency/dispatch families with
        # a `pool` label; monolithic engines add NO label, so every
        # existing exact-label-match consumer is untouched.  The same
        # discipline for sharding: tensor-parallel engines add a `tp`
        # (mesh size) constant-label, single-device engines add nothing
        plabel = {"pool": self.pool_label} if self.pool_label else {}
        if self._sharded is not None:
            plabel["tp"] = str(self._sharded.tp)
        # ...and for fleets: each replica's engine tags the same
        # families with a `replica` constant-label so the merged scrape
        # stays per-replica attributable
        if self.replica_label:
            plabel["replica"] = self.replica_label
        dispatches = MetricFamily(
            "kubeshare_serving_dispatches_total",
            "Device dispatches by kind (mixed = one fused prefill "
            "chunk + decode span, mixed_verify = prefill chunk + "
            "verify chunk, loop = one device-resident multi-step "
            "launch covering loop_units span-units; the standalone "
            "kinds exclude fused work).", "counter")
        dispatches.add({"kind": "prefill_chunk", **plabel},
                       self.prefill_chunks - self.mixed_steps
                       - self.mixed_verify_steps)
        dispatches.add({"kind": "decode_span", **plabel},
                       self.decode_steps - self.mixed_steps
                       - self.loop_units)
        dispatches.add({"kind": "mixed", **plabel}, self.mixed_steps)
        dispatches.add({"kind": "verify_span", **plabel},
                       self.verify_steps - self.mixed_verify_steps
                       - self.spec_loop_units)
        dispatches.add({"kind": "mixed_verify", **plabel},
                       self.mixed_verify_steps)
        dispatches.add({"kind": "loop", **plabel}, self.loop_launches)
        dispatches.add({"kind": "spec_loop", **plabel},
                       self.spec_loop_launches)
        dispatches.add({"kind": "cow_copy", **plabel}, self.cow_copies)
        weight_passes = MetricFamily(
            "kubeshare_serving_weight_passes_total",
            "Passes over the layer stack the dispatched step programs "
            "made, by plan kind: decode_span a decode dispatch, 1 a "
            "prefill chunk, decode_span a mixed dispatch whose chunk "
            "rides the span's first pass and decode_span + 1 one that "
            "runs the chunk and the span back to back (the sharded "
            "context).", "counter")
        for kind, passes in sorted(self.weight_passes.items()):
            weight_passes.add({"kind": kind, **plabel}, passes)
        host_args = MetricFamily(
            "kubeshare_serving_host_args_total",
            "Host arrays the launched step programs' calls carried to "
            "the device: one packed buffer a launch.", "counter")
        host_args.add(dict(plabel), self.host_arg_transfers)
        host_arg_bytes = MetricFamily(
            "kubeshare_serving_host_arg_bytes_total",
            "Bytes of the host arrays the launched step programs' calls "
            "carried to the device.", "counter")
        host_arg_bytes.add(dict(plabel), self.host_arg_bytes)
        loop_units = MetricFamily(
            "kubeshare_serving_loop_units_total",
            "Decode span-units executed inside device-resident loop "
            "launches (units / the loop dispatch count = the realized "
            "fusion depth; at most steps_per_launch per launch).",
            "counter")
        loop_units.add(dict(plabel), self.loop_units)
        spec_loop_units = MetricFamily(
            "kubeshare_serving_spec_loop_units_total",
            "Draft-verify units executed inside speculative device-"
            "resident loop launches (each unit is one in-loop draft + "
            "width-W verify + acceptance round, absorbed into "
            "verify_steps).", "counter")
        spec_loop_units.add(dict(plabel), self.spec_loop_units)
        exit_reason = MetricFamily(
            "kubeshare_serving_loop_exit_reason_total",
            "Device-resident loop launches by exit reason (both loop "
            "kinds): retire = a lane exhausted its budget unrefilled, "
            "stop = a lane hit EOS unrefilled, budget = all K units "
            "ran, redraft = in-loop acceptance collapsed below the "
            "re-draft threshold, ring_empty = a lane died with the "
            "admission ring configured but drained.", "counter")
        for reason in sorted(self.loop_exit_reasons):
            exit_reason.add({"reason": reason, **plabel},
                            self.loop_exit_reasons[reason])
        depth_summary = MetricFamily(
            "kubeshare_serving_loop_realized_depth",
            "Realized fusion depth per device-loop launch (span-units "
            "actually executed, both loop kinds) — the direct summary "
            "a scraper reads instead of dividing counter "
            "families.", "summary")
        depth_summary.samples.append(Sample(
            "kubeshare_serving_loop_realized_depth_sum", dict(plabel),
            self.loop_depth_sum))
        depth_summary.samples.append(Sample(
            "kubeshare_serving_loop_realized_depth_count", dict(plabel),
            self.loop_depth_count))
        host_s = MetricFamily(
            "kubeshare_serving_host_seconds_total",
            "Host wall seconds inside the engine's step loop, by "
            "scheduling phase (admit / consume / plan / dispatch — "
            "dispatch is marshal + launch enqueue on an unguarded "
            "engine).  The numerator of the host-overhead-per-token "
            "ratio the device-resident loop exists to cut.", "counter")
        for phase in sorted(self.host_seconds):
            host_s.add({"phase": phase, **plabel},
                       self.host_seconds[phase])
        # the engine's own guard, where it has the counters (a wrapped or
        # stand-in guard may not): every acquire() is either covered by
        # the held token's budget or goes to the token broker
        guard_wait = MetricFamily(
            "kubeshare_serving_guard_wait_seconds_total",
            "Seconds inside the execution guard's acquire(), by whether "
            "the held token's budget covered the dispatch (held) or the "
            "token broker was asked (broker).", "counter")
        guard_calls = MetricFamily(
            "kubeshare_serving_guard_calls_total",
            "Calls of the execution guard's acquire(), by kind.",
            "counter")
        if hasattr(self.guard, "broker_calls"):
            g = self.guard
            for kind, calls, wait in (
                    ("held", g.acquire_calls - g.broker_calls,
                     g.acquire_wait_s - g.broker_wait_s),
                    ("broker", g.broker_calls, g.broker_wait_s)):
                guard_wait.add({"kind": kind, **plabel}, wait)
                guard_calls.add({"kind": kind, **plabel}, calls)
        slow = MetricFamily(
            "kubeshare_serving_slow_dispatches_total",
            "Gated dispatches that lasted over a second and over eight "
            "times the running estimate, by the phase that took most of "
            "it (acquire / launch / device_wait); each is also one "
            "WARNING line.", "counter")
        for phase in sorted(self.slow_dispatches):
            slow.add({"phase": phase, **plabel},
                     self.slow_dispatches[phase])
        planner = MetricFamily(
            "kubeshare_serving_host_planner_invocations_total",
            "Scheduler planner invocations (_plan_step calls).  With "
            "steps_per_launch=K, invocations per emitted token drop "
            "~K x on decode-heavy phases — the device loop's headline "
            "claim, measured rather than asserted.", "counter")
        planner.add(dict(plabel), self.host_planner_invocations)
        prefix = MetricFamily(
            "kubeshare_serving_prefix_cache_requests_total",
            "Admitted requests by prefix-cache outcome.", "counter")
        hits = self.prefix_hit_requests
        prefix.add({"result": "hit"}, hits)
        prefix.add({"result": "miss"}, self.requests_admitted - hits)
        hit_tokens = MetricFamily(
            "kubeshare_serving_prefix_hit_tokens_total",
            "Prompt tokens whose prefill was skipped via the prefix "
            "cache.", "counter")
        hit_tokens.add({}, self.prefix_hit_tokens)
        evicted = MetricFamily(
            "kubeshare_serving_prefix_evicted_blocks_total",
            "Cached blocks evicted to fund reservations, by reason "
            "(reservation_pressure / quota_drain name the trigger when "
            "the K/V is destroyed; tier_demote / tier_drop name the "
            "host tier's verdict when tiering is on).", "counter")
        for reason in sorted(self.evictions_by_reason):
            evicted.add({"reason": reason},
                        self.evictions_by_reason[reason])
        tier_blocks = MetricFamily(
            "kubeshare_serving_tier_blocks_total",
            "Host-tier block movement: demoted (device -> host), "
            "promoted (host -> device, private partial copies "
            "included), dropped (policy/budget refused the spill), "
            "host_evicted (host entries evicted for host-budget room).",
            "counter")
        tier_blocks.add({"event": "demoted"}, self.tier_demoted_blocks)
        tier_blocks.add({"event": "promoted"}, self.tier_promoted_blocks)
        tier_blocks.add({"event": "dropped"}, self.tier_dropped_blocks)
        tier_blocks.add({"event": "host_evicted"},
                        self.host_tier.evicted_blocks
                        if self.host_tier is not None else 0)
        tier_req = MetricFamily(
            "kubeshare_serving_tier_requests_total",
            "Admitted requests by host-tier outcome (hit = at least "
            "one prompt block recovered from host RAM).", "counter")
        tier_req.add({"result": "hit"}, self.tier_hit_requests)
        tier_req.add({"result": "miss"},
                     self.requests_admitted - self.tier_hit_requests)
        tier_tokens = MetricFamily(
            "kubeshare_serving_tier_hit_tokens_total",
            "Prompt tokens recovered from host-resident blocks.",
            "counter")
        tier_tokens.add({}, self.tier_hit_tokens)
        tier_bytes = MetricFamily(
            "kubeshare_serving_tier_host_bytes",
            "Host-tier occupancy vs budget (serialized wire bytes).",
            "gauge")
        tier_bytes.add({"kind": "used"},
                       self.host_tier.used_bytes
                       if self.host_tier is not None else 0)
        tier_bytes.add({"kind": "budget"},
                       self.host_tier.budget_bytes
                       if self.host_tier is not None else 0)
        tier_stall = MetricFamily(
            "kubeshare_serving_tier_promotion_stall_seconds_total",
            "Host wall time staging promotions (deserialize + upload "
            "enqueue; the device copy-in itself overlaps the pipelined "
            "dispatch on an unguarded engine).", "counter")
        tier_stall.add({}, self.tier_promotion_stall_s)
        tier_corrupt = MetricFamily(
            "kubeshare_serving_tier_corruptions_total",
            "Wire blocks that failed their v2 crc32 at consumption "
            "(tier promotion or migration delivery) — each was dropped "
            "and re-prefilled, never attended into a stream.",
            "counter")
        tier_corrupt.add({}, self.tier_corrupt_blocks)
        tier_origin = MetricFamily(
            "kubeshare_serving_tier_hit_origin_requests_total",
            "Tier-hit admissions split by payload origin: local = "
            "this engine's own demotions (and drain/salvage "
            "inheritance), remote = at least one consumed payload "
            "arrived over the KV fabric.", "counter")
        for org in ("local", "remote"):
            tier_origin.add({"origin": org},
                            self.tier_hit_requests_by_origin[org])
        disk_bytes = MetricFamily(
            "kubeshare_serving_disk_tier_bytes",
            "Disk-tier occupancy vs budget (serialized wire bytes "
            "live in the mmap arena; fragmentation can grow the file "
            "past used, never used past budget).", "gauge")
        disk_bytes.add({"kind": "used"},
                       self.disk_tier.used_bytes
                       if self.disk_tier is not None else 0)
        disk_bytes.add({"kind": "budget"},
                       self.disk_tier.budget_bytes
                       if self.disk_tier is not None else 0)
        disk_blocks = MetricFamily(
            "kubeshare_serving_disk_tier_blocks_total",
            "Disk-tier lifetime events: demoted = HOST→DISK cascades "
            "in, promoted = DISK→HOST stagings out, evicted = "
            "disk-budget LRU drops, refused = puts that found no "
            "room, corrupt_read = payloads whose crc32 failed after a "
            "disk read (dropped, re-prefilled cold).", "counter")
        if self.disk_tier is not None:
            disk_blocks.add({"event": "demoted"},
                            self.disk_tier.stored_blocks)
            disk_blocks.add({"event": "promoted"},
                            self.disk_tier.promoted_blocks)
            disk_blocks.add({"event": "evicted"},
                            self.disk_tier.evicted_blocks)
            disk_blocks.add({"event": "refused"},
                            self.disk_tier.refused_blocks)
            disk_blocks.add({"event": "corrupt_read"},
                            self.disk_tier.corrupt_reads)
        else:
            for ev in ("demoted", "promoted", "evicted", "refused",
                       "corrupt_read"):
                disk_blocks.add({"event": ev}, 0)
        ttft = MetricFamily(
            "kubeshare_serving_ttft_seconds",
            "Time to first token (submit to first emitted token).",
            "histogram")
        _histogram_samples(ttft, "kubeshare_serving_ttft_seconds",
                           dict(plabel), self._ttft_counts,
                           self._ttft_sum)
        # ---- per-tenant QoS families ------------------------------------
        t_depth = MetricFamily(
            "kubeshare_serving_tenant_queue_depth",
            "Queued (unadmitted) requests per tenant.", "gauge")
        for name, depth in self._queue.depths().items():
            t_depth.add({"tenant": name}, depth)
        t_blocks = MetricFamily(
            "kubeshare_serving_tenant_kv_blocks",
            "KV pool blocks charged per tenant (in-use + idle-cached) — "
            "quota occupancy.", "gauge")
        usage = self.allocator.usage_by_tenant
        for name in self.tenants.names():
            t_blocks.add({"tenant": name}, usage.get(name, 0))
        t_tokens = MetricFamily(
            "kubeshare_serving_tenant_tokens_total",
            "Tokens emitted per tenant.", "counter")
        for name in self.tenants.names():
            t_tokens.add({"tenant": name}, self.tenant_tokens.get(name, 0))
        preempt = MetricFamily(
            "kubeshare_serving_preemptions_total",
            "Decode slots preempted, by victim tenant (the victim "
            "resumes via the prefix cache).", "counter")
        for name in self.tenants.names():
            preempt.add({"tenant": name}, self.preemptions.get(name, 0))
        cls_ttft = MetricFamily(
            "kubeshare_serving_ttft_by_class_seconds",
            "Time to first token by QoS class.", "histogram")
        for cls, (counts, total) in sorted(self._ttft_class.items()):
            _histogram_samples(
                cls_ttft, "kubeshare_serving_ttft_by_class_seconds",
                {"qos": cls, **plabel}, counts, total)
        tbt = MetricFamily(
            "kubeshare_serving_tbt_seconds",
            "Inter-token latency by QoS class: wall time between "
            "consecutive host-visible tokens of one request (a span's "
            "burst is attributed evenly across its tokens) — the tail "
            "the mixed scheduler bounds.", "histogram")
        for cls, (counts, total) in sorted(self._tbt_class.items()):
            _histogram_samples(
                tbt, "kubeshare_serving_tbt_seconds",
                {"qos": cls, **plabel}, counts, total, TBT_BUCKETS)
        spec_tokens = MetricFamily(
            "kubeshare_serving_spec_tokens_total",
            "Speculative decoding volume per tenant: drafted = "
            "proposal tokens scored by verify dispatches, accepted = "
            "drafted tokens that reached the stream (the correction "
            "pick is not counted — it is not a draft).", "counter")
        for name in self.tenants.names():
            spec_tokens.add({"tenant": name, "kind": "drafted"},
                            self.spec_drafted.get(name, 0))
            spec_tokens.add({"tenant": name, "kind": "accepted"},
                            self.spec_accepted.get(name, 0))
        coll_bytes = MetricFamily(
            "kubeshare_serving_collective_bytes_total",
            "ESTIMATED fleet-total bytes moved by the collectives "
            "inside sharded dispatches, by kind (shard-shape model, "
            "not a transport measurement; all-zero on a single-device "
            "engine).", "counter")
        for kind in sorted(self.collective_bytes):
            coll_bytes.add({"kind": kind, **plabel},
                           self.collective_bytes[kind])
        spec_accept = MetricFamily(
            "kubeshare_serving_spec_acceptance_ratio",
            "Per-verify-round draft acceptance rate (accepted prefix / "
            "drafted) by tenant — the drafter's hit quality on that "
            "tenant's traffic, and the adaptive width controller's "
            "input.", "histogram")
        for name, (counts, total) in sorted(self._spec_accept.items()):
            _histogram_samples(
                spec_accept, "kubeshare_serving_spec_acceptance_ratio",
                {"tenant": name}, counts, total, SPEC_ACCEPT_BUCKETS)
        tuner = MetricFamily(
            "kubeshare_serving_tuner_decisions_total",
            "Autotuner knob decisions by knob and direction (up / "
            "down = an in-envelope value applied; rejected = the "
            "central sandbox refused an out-of-envelope proposal).  "
            "Empty with autotune off.", "counter")
        if self._tuner is not None:
            for (knob, direction), n in sorted(
                    self._tuner.decisions.items()):
                tuner.add({"knob": knob, "direction": direction,
                           **plabel}, n)
        moe_assign = MetricFamily(
            "kubeshare_serving_moe_assignments_total",
            "Router choices of a routed block's step programs, by where "
            "the chosen expert lives: held (a routed expert this device "
            "holds and computes), zero (a zero-compute identity expert) "
            "or absent (a routed expert another device holds: adds "
            "nothing here).  A padded or an inactive row chooses none.",
            "counter")
        for kind in sorted(self.moe_assignments):
            moe_assign.add({"kind": kind, **plabel},
                           self.moe_assignments[kind])
        moe_touched = MetricFamily(
            "kubeshare_serving_moe_experts_touched_total",
            "Held experts that got at least one row, summed over "
            "expert-layer passes and layers: the experts whose weights a "
            "pass had to read.", "counter")
        moe_touched.add(dict(plabel), self.moe_experts_touched)
        moe_tiles = MetricFamily(
            "kubeshare_serving_moe_tiles_total",
            "Tiles the expert loop of a routed block's step programs "
            "ran: each is one held expert over at most a tile's rows, "
            "and reads that expert's matrices once.", "counter")
        moe_tiles.add(dict(plabel), self.moe_tiles)
        moe_tile_rows = MetricFamily(
            "kubeshare_serving_moe_tile_rows_total",
            "Rows of those tiles, an expert's last tile's padding "
            "included: held assignments over this is how full the tiles "
            "ran.", "counter")
        moe_tile_rows.add(dict(plabel), self.moe_tile_rows)
        retention = []
        for name, value, said in (
                ("state_reads", self.retention_state_reads,
                 "Lane-passes of a 'retention' block's step programs that "
                 "read a lane's recurrent state (a decode lane that has "
                 "folded a key block reads it once a step of a span, a "
                 "prefill chunk's lane once)."),
                ("tail_rows", self.retention_tail_rows,
                 "Unfolded rows those programs read as keys and values, "
                 "summed over lanes and passes: the paged tails beside "
                 "the states."),
                ("folds", self.retention_folds,
                 "Key blocks folded into a lane's state by a step "
                 "program's last phase."),
                ("pages_freed", self.retention_pages_freed,
                 "Pages handed back to the allocator behind those folds, "
                 "less the pages the same lanes drew for the rows "
                 "ahead.")):
            family = MetricFamily(
                f"kubeshare_serving_retention_{name}_total", said,
                "counter")
            family.add(dict(plabel), value)
            retention.append(family)
        for name, value, said in (
                ("state_reads", self.conv_state_reads,
                 "Lane-passes x convolution layers of a model with short "
                 "convolutions that read a slot's state (a decode lane "
                 "once a step of a span, a prefill chunk's lane once; a "
                 "chunk that begins at row 0 reads zeros instead)."),
                ("state_resets", self.conv_state_resets,
                 "Prefill chunks that began at row 0: their lanes' "
                 "states start from zeros whatever the slot held (a new "
                 "request in a reused slot, a preempted one resumed).")):
            family = MetricFamily(
                f"kubeshare_serving_conv_{name}_total", said, "counter")
            family.add(dict(plabel), value)
            retention.append(family)
        if self.kv_kind_blocks:
            family = MetricFamily(
                "kubeshare_serving_kv_kind_blocks_total",
                "Pages of a cache by layer kind, by kind (full: every row "
                "of a request under one table; window: the rows a window "
                "layer still reaches) and event: reserved at admission, "
                "returned at a request's end or preemption and, the window "
                "kind's, released behind the window and drawn for the rows "
                "ahead while the request runs.", "counter")
            for (kind, event), value in self.kv_kind_blocks.items():
                family.add({**plabel, "kind": kind, "event": event}, value)
            retention.append(family)
        diff_passes = MetricFamily(
            "kubeshare_serving_diffusion_passes_total",
            "Lane-passes of a configuration that generates by diffusion "
            "over blocks, by kind: denoise (a pass over a block with "
            "masked rows: it may commit some) or commit (the pass over a "
            "finished block, whose K/V stay in the pool).", "counter")
        for kind in sorted(self.diffusion_passes):
            diff_passes.add({"kind": kind, **plabel},
                            self.diffusion_passes[kind])
        diff_rows = MetricFamily(
            "kubeshare_serving_diffusion_rows_total",
            "Query rows those lane-passes computed (a block's rows each "
            "pass).", "counter")
        diff_rows.add(dict(plabel), self.diffusion_rows)
        diff_tokens = MetricFamily(
            "kubeshare_serving_diffusion_tokens_committed_total",
            "Tokens the denoising passes committed: each is served, and "
            "counted in tokens_generated, once, then.", "counter")
        diff_tokens.add(dict(plabel), self.diffusion_tokens_committed)
        diff_blocks = MetricFamily(
            "kubeshare_serving_diffusion_blocks_total",
            "Diffusion blocks finished and committed to the pool.",
            "counter")
        diff_blocks.add(dict(plabel), self.diffusion_blocks)
        view_rows = MetricFamily(
            "kubeshare_serving_view_rows_total",
            "View rows a lane of the step programs' attention, summed "
            "over planned dispatches: reached (the furthest lane's rows "
            "at launch, rounded up to key blocks: what the attention ran "
            "over), configured (max_request_len: what it would run "
            "over whatever the lanes hold) and held (the decode lanes' "
            "own rows, all of them: what the paged kernel reads).",
            "counter")
        view_rows.add({"kind": "reached", **plabel}, self.view_rows_reached)
        view_rows.add({"kind": "configured", **plabel},
                      self.view_rows_configured)
        view_rows.add({"kind": "held", **plabel}, self.view_rows_held)
        return [req, blocks, tokens, dispatches, weight_passes, host_args,
                host_arg_bytes, loop_units,
                moe_assign, moe_touched, moe_tiles, moe_tile_rows, view_rows,
                diff_passes, diff_rows, diff_tokens, diff_blocks,
                *retention, spec_loop_units, exit_reason, depth_summary,
                host_s,
                guard_wait, guard_calls, slow, planner, prefix,
                hit_tokens, evicted, tier_blocks,
                tier_req, tier_tokens, tier_bytes, tier_stall,
                tier_corrupt, tier_origin, disk_bytes, disk_blocks, ttft,
                t_depth, t_blocks, t_tokens, preempt, cls_ttft, tbt,
                coll_bytes, spec_tokens, spec_accept, tuner]

    def serve_metrics(self, port: int = 0) -> MetricServer:
        """Start the textfile HTTP scrape endpoint (``/metrics`` and
        ``/kubeshare-serving``); returns the started server (its
        ``.port`` is the bound port — pass 0 for ephemeral)."""
        server = MetricServer(self.collect_metrics, port=port,
                              path="/kubeshare-serving")
        server.start()
        return server

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _observe_ttft(self, seconds: float, tenant: str) -> None:
        self._ttft_sum += seconds
        cls = self._ttft_class[self.tenants.get(tenant).qos_class]
        cls[1] += seconds
        _bucket_observe(self._ttft_counts, seconds)
        _bucket_observe(cls[0], seconds)

    def _observe_tbt(self, per_token: float, count: int,
                     tenant: str) -> None:
        """Record ``count`` inter-token gaps of ``per_token`` seconds
        each (a span's tokens become host-visible in one burst; the
        burst's wall gap is attributed evenly)."""
        cls = self._tbt_class[self.tenants.get(tenant).qos_class]
        cls[1] += per_token * count
        _bucket_observe(cls[0], per_token, TBT_BUCKETS, count)

    # ------------------------------------------------------------------
    # KV tiering internals (kv_tier.py owns the store; the engine owns
    # the glue between allocator eviction, the trie, and the pool)
    # ------------------------------------------------------------------
    def _evict_blocks(self, victim: int, reason: str) -> List[int]:
        """The allocator's eviction callback.  Tiering off: detach the
        victim's subtree from the trie (the K/V is destroyed) and count
        the trigger ``reason``.  Tiering on: walk the subtree through
        the TierPolicy — each node is DEMOTED (serialized into the host
        tier, trie node kept HOST-resident) or DROPPED (subtree
        detached, pre-tier behavior).  Either way every device block in
        the subtree is released to the allocator, which uncharges it
        from its tenant's quota ledger — a demoted cache stops
        occupying the HBM budget of whoever brought it in (the quota-
        honesty fix; re-charging happens at promotion, which is a
        normal tenant reservation).  Runs UNDER the allocator lock: no
        locking allocator methods may be called from here."""
        if self.host_tier is None:
            removed = self.prefix_index.evict(victim)
            self.evictions_by_reason[reason] += len(removed)
            return removed
        released: List[int] = []
        # entries demoted WITHIN this walk are pinned until it returns:
        # the walk goes parent-first, so a just-demoted ancestor
        # transiently has device-resident children — if a descendant's
        # put() picked it as the budget victim, _drop_host_entry would
        # detach a subtree that still holds device blocks (review
        # regression: crashed under a one-block host budget)
        walk_pins: List[int] = []
        try:
            self._tier_visit(self.prefix_index.node_of(victim), released,
                             walk_pins)
        finally:
            for k in walk_pins:
                self.host_tier.unpin(k)
        return released

    def _read_block_payload(self, node) -> bytes:
        """Serialize one device block's K/V rows + token run.  Reading
        the pool synchronizes with any in-flight dispatch (the pool
        arrays are its outputs) — demotion is an eviction-pressure
        path, not a hot path."""
        k_slab = np.asarray(self.pool.k[:, node.block])
        v_slab = np.asarray(self.pool.v[:, node.block])
        return pack_block(node.tokens, k_slab, v_slab)

    def _tier_visit(self, root, released: List[int],
                    walk_pins: List[int]) -> None:
        """Demote-or-drop every device-resident node in ``root``'s
        subtree, parent-first (host children are already spilled).  A
        dropped node takes its whole subtree with it — descendants
        below a detached node could never be matched again, so demoting
        them would only leak host bytes.  Demoted keys are pinned into
        ``walk_pins`` (released by the caller): the parent-first order
        means a demoted ancestor still has device children mid-walk,
        and the tier must not evict it to fund them.  Iterative like
        ``PrefixIndex.detach`` — subtree depth is bounded only by
        ``max_request_len / block_size``, far past Python's recursion
        limit on long-context configs."""
        stack = [root] if root is not None else []
        while stack:
            node = stack.pop()
            # under the allocator lock: read the charge ledger directly
            tenant = self.allocator._tenant_of.get(node.block)
            payload = self._read_block_payload(node)
            key = self.host_tier.put(payload, tenant, node)
            if key is None:
                device, host_keys, disk_keys = \
                    self.prefix_index.detach(node)
                for hk in host_keys:
                    self.host_tier.forget(hk)
                for dk in disk_keys:
                    self.disk_tier.forget(dk)
                released.extend(device)
                self.tier_dropped_blocks += len(device)
                self.evictions_by_reason["tier_drop"] += len(device)
                continue
            self.host_tier.pin(key)
            walk_pins.append(key)
            released.append(node.block)
            self.prefix_index.demote(node.block, key)
            self.tier_demoted_blocks += 1
            self.evictions_by_reason["tier_demote"] += 1
            if self.on_tier_demote is not None:
                # disagg cross-pool cache bus: mirror the payload into
                # the PEER pool's trie (pure host work — safe under the
                # allocator lock; the router never touches THIS pool)
                self.on_tier_demote(node, payload, tenant)
            stack.extend(
                child
                for child in list(node.children.values()) + node.partials
                if child.block >= 0)

    def _spill_host_entry(self, entry) -> None:
        """HostTier's budget-eviction hook.  With a disk tier below,
        the evicted payload CASCADES (HOST→DISK): the bytes move into
        the mmap arena, the trie node transitions to DISK-resident,
        and the prefix stays matchable — a disk read + staging away
        from promotion instead of a re-prefill.  Without one (or when
        the disk refuses), the entry is destroyed the pre-disk way."""
        if self.disk_tier is not None and entry.node is not None:
            dkey = self.disk_tier.put(entry.payload, entry.tenant,
                                      entry.node, origin=entry.origin)
            if dkey is not None:
                self.prefix_index.to_disk(entry.node, dkey)
                self.host_tier.forget(entry.key)
                return
        self._drop_host_entry(entry)

    def _drop_host_entry(self, entry) -> None:
        """Destroy a host entry: its trie node (and the node's
        all-non-device subtree) goes with it — the cascade's forgets
        free the bytes.  The corrupt-payload path calls this directly
        (never :meth:`_spill_host_entry` — rotted bytes must not be
        parked on disk)."""
        device, host_keys, disk_keys = self.prefix_index.detach(entry.node)
        if device:  # non-device-below-device invariant violated
            raise RuntimeError(
                f"host entry {entry.key}'s subtree held device blocks "
                f"{device} — index/tier state diverged")
        for hk in host_keys:
            self.host_tier.forget(hk)
        for dk in disk_keys:
            self.disk_tier.forget(dk)

    def _drop_disk_entry(self, entry) -> None:
        """DiskTier's budget-eviction hook: the end of the cascade —
        nothing below disk, so the entry's subtree detaches and every
        tier copy in it is purged."""
        device, host_keys, disk_keys = self.prefix_index.detach(entry.node)
        if device:
            raise RuntimeError(
                f"disk entry {entry.key}'s subtree held device blocks "
                f"{device} — index/tier state diverged")
        for hk in host_keys:
            self.host_tier.forget(hk)
        for dk in disk_keys:
            self.disk_tier.forget(dk)

    def _validate_host_hit(self, hit: _PrefixHit):
        """Deserialize (and crc-check) every host payload ``hit`` will
        consume, returning ``{host_key: (tokens, k_slab, v_slab)}`` —
        or None after dropping the corrupt entries (tier forget + trie
        detach, counted in ``tier_corrupt_blocks``), in which case the
        caller must retry the admission cold.  Validation-before-upload
        is the point: a corrupt middle block detected after its
        siblings uploaded would leave a half-promoted slot."""
        slabs, bad = {}, []
        nodes = list(hit.promote)
        if hit.host_cow is not None:
            nodes.append(hit.host_cow)
        for node in nodes:
            entry = self.host_tier.probe(node.host_key)
            try:
                slabs[node.host_key] = unpack_block(entry.payload)
            except WireCorruption:
                bad.append(entry)
        if not bad:
            return slabs
        for entry in bad:
            self.tier_corrupt_blocks += 1
            if self.host_tier.probe(entry.key) is not None:
                # a corrupt ancestor's detach may have already cascaded
                # this entry out of the tier
                self._drop_host_entry(entry)
        return None

    def _match_prefix(self, pending: _Pending,
                      limit: Optional[int] = None) -> Optional[_PrefixHit]:
        """Admission-time prefix lookup for one queued request (None =
        cold).  The tier-aware trie walk may cross HOST- and DISK-
        resident nodes: device full matches map as shared blocks,
        host/disk full matches become promotions (disk ones are staged
        to host first — :meth:`_stage_disk_hit`), and a partial tail
        match routes to the CoW copy (device) or a private payload
        upload (host/disk).  The matched-token cap (prompt - 1) keeps
        at least one real token in the prefill plan — its logits row IS
        the first output token.  ``limit`` additionally caps the match
        (the disk-staging retry path truncates before a block the host
        tier could not stage)."""
        ec = self.engine_config
        prompt = pending.prompt
        matched, chain = self.prefix_index.match_tiered(prompt)
        if self._diffusion:
            # under the block-causal mask a row's keys depend on its
            # whole block: a match is good for the whole diffusion blocks
            # it covers, of those the prompt prefills
            b = self._diffusion
            matched = min(matched, prompt.size) // b * b
        else:
            matched = min(matched, prompt.size - 1)
        if limit is not None:
            matched = min(matched, limit)
        if matched <= 0:
            return None
        chain = chain[: self.allocator.blocks_for_tokens(matched)]
        n_full = matched // ec.block_size
        partial = matched % ec.block_size
        shared: List[int] = []
        promote: List = []
        for node in chain[:n_full]:
            if node.block >= 0:
                if promote:  # non-device-ness is downward-closed
                    raise RuntimeError(
                        "device-resident node below a tiered one "
                        "in a match chain — index/tier state diverged")
                shared.append(node.block)
            else:
                promote.append(node)
        cow_src = host_cow = None
        if partial:
            tail = chain[n_full]
            if tail.block >= 0:
                cow_src = tail.block
            else:
                host_cow = tail
        plan, cover = self._prefill_plan(prompt.size, matched)
        total_rows = self._lifetime_rows(prompt.size, pending.max_new,
                                         cover)
        needed = (self.allocator.blocks_for_tokens(total_rows)
                  - len(shared))
        host_tokens = (len(promote) * ec.block_size
                       + (partial if host_cow is not None else 0))
        return _PrefixHit(matched, shared, cow_src, promote, host_cow,
                          plan, needed, host_tokens)

    def _admit(self) -> None:
        """QoS admission: walk tenants in fair-queue order (Guarantee
        class first, lowest decayed service/weight within a class) and
        pop each tenant's head into a free slot while the allocator can
        fund it.  WITHIN a tenant head-of-line blocking is deliberate —
        skipping ahead would starve its large requests forever — but a
        tenant blocked on its OWN quota is skipped so the rest of the
        pool keeps flowing.  A Guarantee head the POOL cannot fund
        preempts Opportunistic decode slots (cache-backed: see
        :meth:`_preempt`) until it fits or no victims remain.

        With the prefix cache, admission first walks the prompt down the
        radix index and RETAINS every matched block (refcount +1 — a
        retained block cannot be evicted by the reservation that
        follows), then reserves only the blocks the uncached suffix
        needs.  A partially matched tail block is copied-on-write into
        the first fresh block before the slot may append to it."""
        # device residency v2: ring-staged requests the loop did NOT
        # activate (it exited first) enter through the normal slot path
        # — each is already admitted and prefilled, so binding is a
        # pure field copy into a free lane.  Guarded against a still-
        # in-flight spec loop: its consume may yet activate these
        # entries on device, and a host-side bind here would double-
        # serve them.
        if self._ring_staged and (self._inflight is None
                                  or self._inflight[0] != "spec_loop"):
            for staged in list(self._ring_staged):
                slot = next((s for s in self._slots
                             if s.state == "free"), None)
                if slot is None:
                    break
                self._bind_staged(staged, slot)
                self._ring_staged.remove(staged)
        while True:
            if self.admission_gate is not None \
                    and not self.admission_gate():
                return
            order = self._queue.order()
            if not order:
                return
            progressed = False
            for tenant in order:
                spec = self.tenants.get(tenant)
                free = [s for s in self._slots if s.state == "free"]
                if not free:
                    # no slot for ANY tenant; a Guarantee head may take
                    # one from an Opportunistic decode, everyone else
                    # waits for a retirement.  A head blocked on its OWN
                    # quota must not preempt (a victim's slot cannot
                    # cure a quota block — it would thrash one victim
                    # per tick); skip it like the "quota" outcome below.
                    if self._quota_blocked(self._queue.peek(tenant), spec):
                        continue
                    if spec.is_guarantee and self._preempt_victim():
                        free = [s for s in self._slots
                                if s.state == "free"]
                        progressed = True
                        if not free:
                            # consuming the in-flight span made progress
                            # but freed no slot; re-walk before actually
                            # preempting anyone
                            break
                    else:
                        return
                outcome = self._try_admit(self._queue.peek(tenant), spec,
                                          free[0])
                if outcome == "admitted":
                    self._queue.pop(tenant)
                    progressed = True
                    break
                if outcome == "quota":
                    continue  # this tenant's own limit; try the next
                # pool exhausted: Guarantee preempts, everyone else
                # stops here (admitting a lower-ranked tenant past a
                # blocked head would invert the fair order)
                if spec.is_guarantee and self._preempt_victim():
                    progressed = True
                    break
                return
            if not progressed:
                return

    def _bind_staged(self, staged: _Slot, slot: _Slot) -> None:
        """Bind one ring-staged (admitted + prefilled) request into a
        real engine lane: a pure field copy — every piece of engine-
        global state (allocator charges, results map, counters, queue
        service) was already mutated when the staged slot passed
        :meth:`_try_admit` and its synchronous prefill."""
        for name in _Slot.__slots__:
            if name in ("idx", "table"):
                continue
            setattr(slot, name, getattr(staged, name))
        slot.table[:] = staged.table

    def _quota_blocked(self, pending: _Pending, spec: TenantSpec) -> bool:
        """Would admitting ``pending`` fail on its tenant's OWN quota
        both ways _try_admit can attempt it (prefix hit AND cold)?
        Side-effect-free (the prefix match only reads the trie): asks
        the allocator's dry-run gate with the blocks each path would
        request, excluding to-be-retained shared blocks from the
        drainable set on the hit path."""
        if spec.kv_block_quota is None:
            return False
        if self.allocator.quota_can_fit(
                pending.needed, spec.name, spec.kv_block_quota):
            return False  # the cold fallback fits
        if self.prefix_index is not None:
            hit = self._match_prefix(pending)
            if hit is not None and self.allocator.quota_can_fit(
                    hit.needed, spec.name, spec.kv_block_quota,
                    keep=hit.shared + ([hit.cow_src]
                                       if hit.cow_src is not None
                                       else [])):
                return False
        return True

    def _stage_disk_hit(self, pending: _Pending) -> Optional[_PrefixHit]:
        """Match + DISK→HOST staging: re-home every disk-resident node
        the hit would consume into the host tier (read, crc-validate,
        put, pin) so the promotion path below sees only host payloads.
        Staging fires at trie-match time, BEFORE the reservation: on an
        unguarded engine the uploads that follow overlap the in-flight
        pipelined dispatch (the prefetch overlap the disk tier leans
        on).  A corrupt disk read drops the node's subtree and
        re-matches — a shorter or cold admission, never wrong tokens;
        a host tier that cannot take a staged payload truncates the
        match just before that block."""
        limit: Optional[int] = None
        staged_pins: List[int] = []
        try:
            hit = self._match_prefix(pending, limit)
            while hit is not None:
                nodes = list(hit.promote)
                if hit.host_cow is not None:
                    nodes.append(hit.host_cow)
                disk_nodes = [n for n in nodes if n.disk_key is not None]
                if not disk_nodes:
                    return hit
                t0 = time.monotonic()
                clean = True
                for node in disk_nodes:
                    dkey = node.disk_key
                    entry = self.disk_tier.probe(dkey)
                    payload = self.disk_tier.read(dkey)
                    try:
                        unpack_block(payload)
                    except WireCorruption:
                        # rot on the platter (or the chaos read seam):
                        # the node's subtree is unusable — drop it and
                        # re-match what is left
                        self.disk_tier.corrupt_reads += 1
                        self.tier_corrupt_blocks += 1
                        self._drop_disk_entry(entry)
                        clean = False
                        break
                    hkey = self.host_tier.put(payload, entry.tenant,
                                              node, origin=entry.origin)
                    if hkey is None:
                        # host refused (budget/pins): the block stays
                        # on disk; truncate the match before it
                        before = (len(self.prefix_index.path_tokens(node))
                                  - len(node.tokens))
                        limit = (before if limit is None
                                 else min(limit, before))
                        clean = False
                        break
                    # pinned through the rest of staging — a later put
                    # must not cascade this one straight back to disk
                    self.host_tier.pin(hkey)
                    staged_pins.append(hkey)
                    self.prefix_index.stage_to_host(node, hkey)
                    self.disk_tier.forget(dkey)
                    self.disk_tier.promoted_blocks += 1
                self.tier_promotion_stall_s += time.monotonic() - t0
                if clean:
                    # every disk node in the hit is host-resident now;
                    # the hit's node objects reflect it in place
                    return hit
                hit = self._match_prefix(pending, limit)
            return None
        finally:
            # _try_admit re-pins what the hit consumes through its own
            # pinned list (and nothing touches the tier in between)
            for k in staged_pins:
                self.host_tier.unpin(k)

    def _try_admit(self, pending: _Pending, spec: TenantSpec,
                   slot: _Slot) -> str:
        """Try to admit one queued request into ``slot``; returns
        "admitted", "quota" (the tenant's own cap — skippable), or
        "pool" (global shortfall).  A failed attempt rolls back every
        retained block."""
        plan, needed = pending.plan, pending.needed
        if self.prefix_index is None:
            hit = None
        elif self.disk_tier is not None:
            hit = self._stage_disk_hit(pending)
        else:
            hit = self._match_prefix(pending)
        if hit is not None:
            plan, needed = hit.plan, hit.needed
        evict_first = (set(self.tenants.opportunistic())
                       if spec.is_guarantee else None)
        while True:
            shared = hit.shared if hit is not None else []
            cow_src = hit.cow_src if hit is not None else None
            retained = shared + ([cow_src] if cow_src is not None else [])
            pinned: List[int] = []
            if hit is not None and self.host_tier is not None:
                # the reserve below may demote MORE blocks into the
                # host tier, and the tier's budget eviction must not
                # take the entries this admission is about to promote
                pinned = [n.host_key for n in hit.promote]
                if hit.host_cow is not None:
                    pinned.append(hit.host_cow.host_key)
                for k in pinned:
                    self.host_tier.pin(k)
            if retained:
                self.allocator.retain(retained)
            try:
                blocks = self.allocator.reserve(
                    needed, pending.rid, tenant=spec.name,
                    quota=spec.kv_block_quota,
                    evict_tenants_first=evict_first)
                if self._kinds:
                    # the window kind, as explicit and as up-front: what a
                    # lane of it can ever hold at once, or the request's
                    # pages if those are fewer (a tenant's quota counts
                    # the full kind's pages alone)
                    try:
                        near = self.window_allocator.reserve(
                            min(needed, self._window_pages), pending.rid,
                            tenant=spec.name)
                    except BlockExhausted:
                        self.allocator.reclaim(blocks)
                        raise
                # host payloads are deserialized (and crc-checked) here,
                # BEFORE any of them uploads: a corrupt block is dropped
                # from tier + trie and the whole admission retries COLD —
                # a rotted host byte costs a re-prefill, never a
                # partially-promoted slot or a corrupted stream
                slabs = (self._validate_host_hit(hit)
                         if hit is not None
                         and (hit.promote or hit.host_cow is not None)
                         else {})
                if slabs is None:
                    for k in pinned:
                        self.host_tier.unpin(k)
                    self.allocator.reclaim(blocks)
                    if retained:
                        self.allocator.reclaim(retained)
                    hit = None
                    plan, needed = pending.plan, pending.needed
                    continue
                break
            except QuotaExceeded:
                for k in pinned:
                    self.host_tier.unpin(k)
                if retained:
                    self.allocator.reclaim(retained)
                if hit is not None:
                    # a prefix HIT can be quota-infeasible where a cold
                    # admission is not: the retained chain (+ transient
                    # CoW source) pins charged blocks the quota drain
                    # may not touch, so a request sized exactly to its
                    # quota would re-block on its own cache every tick.
                    # Retry cold — the hit saves FLOPs, never
                    # correctness, and the cold reserve may now evict
                    # the matched chain itself.
                    hit = None
                    plan, needed = pending.plan, pending.needed
                    continue
                return "quota"
            except BlockExhausted:
                for k in pinned:
                    self.host_tier.unpin(k)
                if retained:
                    self.allocator.reclaim(retained)
                return "pool"
        slot.state = "prefill"
        slot.rid = pending.rid
        slot.tenant = spec.name
        # table order: [device shared prefix | promoted host blocks
        # (blocks[:n_promote], chain order) | CoW / host-partial copy
        # (blocks[n_promote], when the match ends mid-block) | fresh
        # suffix blocks]
        n_promote = len(hit.promote) if hit is not None else 0
        slot.blocks = shared + blocks
        slot.table[:] = 0
        slot.table[: len(slot.blocks)] = slot.blocks
        slot.length = 0
        slot.folded, slot.paged_to = 0, len(slot.blocks)
        if self._kinds:
            first = self._table_width
            slot.table[first:first + len(near)] = near
            slot.window_blocks, slot.window_to = list(near), len(near)
            self.kv_kind_blocks["full", "reserved"] += len(blocks)
            self.kv_kind_blocks["window", "reserved"] += len(near)
        if n_promote or (hit is not None and hit.host_cow is not None):
            # PROMOTION: host payloads back into fresh device blocks.
            # Each upload is one warmed compiled shape dispatched
            # through the pipelined path — on an unguarded engine the
            # copy-in overlaps the in-flight decode dispatch, so lanes
            # keep advancing while the prefix re-materializes.  The
            # stall counter records the host-side staging time
            # (deserialize + enqueue; plus device sync when guarded).
            t0 = time.monotonic()
            # remote-vs-local split: a hit is "remote" when ANY payload
            # it consumes was adopted over the fabric (probe before the
            # takes below surrender the entries)
            origin = "local"
            for node in hit.promote + ([hit.host_cow]
                                       if hit.host_cow is not None
                                       else []):
                e = self.host_tier.probe(node.host_key)
                if e is not None and e.origin == "remote":
                    origin = "remote"
                    break
            for node, dst in zip(hit.promote, blocks[:n_promote]):
                entry = self.host_tier.take(node.host_key)
                _, k_slab, v_slab = slabs[node.host_key]
                pk, pv = self._dispatch(
                    self._upload_step, self.pool.k, self.pool.v,
                    jnp.asarray(dst, jnp.int32),
                    jnp.asarray(k_slab), jnp.asarray(v_slab))
                self.pool = replace(self.pool, k=pk, v=pv)
                self.prefix_index.promote(node, dst)
            if n_promote:
                # promoted blocks are trie-referenced again: park
                # idle-cached at release, like any indexed block.  The
                # reserve above already re-charged them to the tenant.
                self.allocator.mark_cached(blocks[:n_promote])
            if hit.host_cow is not None:
                # host partial match: the payload goes STRAIGHT into
                # the request's private tail block (the host twin of
                # the CoW copy); the entry stays host-side serving
                # other matchers
                entry = self.host_tier.peek(hit.host_cow.host_key)
                _, k_slab, v_slab = slabs[hit.host_cow.host_key]
                pk, pv = self._dispatch(
                    self._upload_step, self.pool.k, self.pool.v,
                    jnp.asarray(blocks[n_promote], jnp.int32),
                    jnp.asarray(k_slab), jnp.asarray(v_slab))
                self.pool = replace(self.pool, k=pk, v=pv)
                # peek leaves the entry host-side, so take()'s promote
                # metering never sees this copy-out — meter it here
                self.host_tier.meter(entry.nbytes, "promote")
            self.tier_promoted_blocks += n_promote + (
                1 if hit.host_cow is not None else 0)
            self.tier_promotion_stall_s += time.monotonic() - t0
            self.tier_hit_requests += 1
            self.tier_hit_requests_by_origin[origin] += 1
            self.tier_hit_tokens += hit.host_tokens
        for k in pinned:
            self.host_tier.unpin(k)
        if cow_src is not None:
            pk, pv = self._dispatch(
                self._copy_step, self.pool.k, self.pool.v,
                jnp.asarray(cow_src, jnp.int32),
                jnp.asarray(blocks[n_promote], jnp.int32))
            self.pool = replace(self.pool, k=pk, v=pv)
            self.allocator.reclaim([cow_src])  # transient read ref
            self.cow_copies += 1
        if hit is not None:
            # honest skip count: the bucketed tail may slide BELOW
            # the match point (or a tiny prompt replans from 0),
            # re-prefilling cached rows — only rows no plan chunk
            # rewrites were actually skipped
            skipped = min([hit.start] + [s for s, _, _ in plan])
            self.prefix_hit_requests += 1
            self.prefix_hit_tokens += skipped
        self.requests_admitted += 1
        slot.generated = []
        slot.emitted_prefix = list(pending.emitted)
        slot.last_token_at = pending.last_token_at
        slot.prompt = pending.prompt
        slot.plan = list(plan)
        slot.max_new = pending.max_new
        slot.temperature = pending.temperature
        if pending.first_key is not None:
            # resumed after preemption: the remaining key schedule rides
            # with the pending entry (re-splitting rng would re-issue
            # keys the first incarnation already consumed)
            slot.first_key = pending.first_key
            slot.step_keys = pending.step_keys
        elif pending.temperature > 0.0:
            # EXACTLY sample_decode_with_cache's key schedule: one
            # split for the first token, then the step keys in bulk
            rng, first_key = jax.random.split(pending.rng)
            slot.first_key = np.asarray(first_key)
            slot.step_keys = (
                np.asarray(jax.random.split(rng, pending.max_new - 1))
                if pending.max_new > 1 else
                np.zeros((0, 2), np.uint32))
        else:
            slot.first_key = np.zeros((2,), np.uint32)
            slot.step_keys = np.zeros((0, 2), np.uint32)
        slot.result = self._results[pending.rid]
        if slot.result.admitted_at is None:
            slot.result.admitted_at = time.monotonic()
        if self._diffusion:
            slot.block = pending.block
            if not slot.plan:
                # nothing to prefill (a prompt shorter than a block, or
                # one the prefix cache covers): straight to its passes
                self._begin_diffusion(slot)
        ec = self.engine_config
        if ec.speculative:
            # drafting state: the lane's lookup window starts as its
            # prompt — for a resumed request that IS prompt + generated,
            # so the rebuilt drafter sees the identical window an
            # unpreempted lane would.  Width starts optimistic at the
            # full draft_len — a wide verify is still ONE dispatch, so
            # over-drafting costs compute but never dispatches, while
            # under-drafting a loopy lane forfeits emissions; lanes
            # whose proposals miss halve down within a few rounds of
            # the acceptance EMA.
            slot.drafter = NGramDrafter(ec.draft_ngram, pending.prompt)
            slot.draft_width = min(ec.draft_len, self._draft_width_cap)
            slot.accept_rate = 0.5
            if self.prefix_index is not None:
                # a cache-hit lane has seen this movie: the trie's
                # cached continuation of the prompt is a second lookup
                # window (a previous request's generation predicts a
                # re-run's)
                cont = self.prefix_index.continuation(
                    pending.prompt, 4 * ec.draft_len)
                if cont:
                    slot.drafter.hint(list(pending.prompt) + cont)
        self.peak_blocks_in_use = max(
            self.peak_blocks_in_use, self.allocator.blocks_in_use)
        return "admitted"

    def _preempt_victim(self) -> bool:
        """Pick and preempt one Opportunistic DECODE slot for a starved
        Guarantee admission; returns False when none exists.  Victim
        choice: the slot holding the most blocks (each preemption frees
        the most HBM, so a Guarantee admission needs the fewest victims);
        highest slot index breaks ties deterministically.  Prefill-state
        slots are never preempted — their prompt is mid-write and worth
        nothing to the cache yet."""
        # fresh state first: an unconsumed in-flight span may have
        # already retired slots or advanced the would-be victim —
        # preempting on stale state would build a wrong resume prompt,
        # and consuming may free what admission needed without any
        # preemption at all.  When it did something, report progress
        # and let the admission loop retry before sacrificing anyone.
        if self._consume_inflight():
            return True
        victims = [
            s for s in self._slots
            if s.state == "decode"
            and not self.tenants.get(s.tenant).is_guarantee]
        if not victims:
            return False
        self._preempt(max(victims, key=lambda s: (len(s.blocks), s.idx)))
        return True

    def _preempt(self, slot: _Slot) -> None:
        """Cache-backed preemption: retire the victim's prompt AND
        generated blocks into the prefix index, free its slot, and
        re-queue the remainder at the front of its tenant's lane.

        The cache holds K/V for positions ``0 .. slot.length - 1`` =
        ``prompt + generated[:-1]`` (the newest emitted token's K/V is
        written by the NEXT decode step), so exactly that sequence is
        indexed.  The resume request's prompt is ``prompt + generated``
        — its last token is the first uncached one, so re-admission's
        trie walk restarts prefill right there and the continuation is
        bit-exact (sampled requests carry their remaining key schedule:
        emission k of the original consumes ``step_keys[k-1]``, which
        becomes the resumed request's ``first_key``).

        A DIFFUSION lane resumes from its last committed block by the
        same arithmetic: the pool holds rows ``0 .. slot.length - 1``
        (the prompt's whole blocks and the finished generated blocks,
        each left by the pass over the finished block), ``generated``
        holds the finished blocks' tokens, and the unfinished block's
        passes left nothing in the pool — its state (what is committed,
        what is masked, the pass it is at) rides with the request, so
        the continuation's passes are the unpreempted run's and a token
        served before the preemption is not served, or counted, again."""
        done = len(slot.generated)  # >= 1 in decode state, but for a
        # diffusion lane still in its first generated block
        resume_prompt = np.concatenate(
            [slot.prompt, np.asarray(slot.generated, np.int32)])
        if self.prefix_index is not None and slot.length:
            n_cached = self.allocator.blocks_for_tokens(slot.length)
            cached_blocks = [int(b) for b in slot.table[:n_cached]]
            newly_cached, displaced = self.prefix_index.insert(
                resume_prompt[:slot.length], cached_blocks)
            self.allocator.mark_cached(newly_cached)
            for b in displaced:
                self.allocator.uncache(b)
        # reclaim TAIL-first: idle-LRU order then evicts the chain's
        # deepest block (a leaf subtree) before its head — a following
        # reservation that needs only a few blocks shaves the cached
        # chain instead of wiping it, so the resume still hits
        self.allocator.reclaim(slot.blocks[::-1])
        # both kinds' pages are dropped whole: the resumed request
        # prefills prompt + generated from row 0
        self._return_window(slot)
        remaining = slot.max_new - done
        plan, cover = self._prefill_plan(resume_prompt.size)
        rows = max(cover, self._request_rows(resume_prompt.size, remaining))
        if self._retention:
            # the state is DROPPED with the pages (no tier holds one, and
            # the index none of its rows): the resumed request prefills
            # prompt + generated from row 0 and folds its way back
            rows = min(rows, self._tail_rows)
        needed = self.allocator.blocks_for_tokens(rows)
        if slot.temperature > 0.0:
            first_key = np.asarray(slot.step_keys[done - 1])
            step_keys = np.asarray(slot.step_keys[done:])
        else:
            first_key = np.zeros((2,), np.uint32)
            step_keys = np.zeros((0, 2), np.uint32)
        pending = _Pending(
            rid=slot.rid, tenant=slot.tenant, prompt=resume_prompt,
            max_new=remaining, temperature=slot.temperature,
            plan=plan, needed=needed, first_key=first_key,
            step_keys=step_keys,
            emitted=slot.emitted_prefix + slot.generated,
            last_token_at=slot.last_token_at, block=slot.block)
        if self.on_preempt_requeue is not None:
            # disagg: the resume must re-prefill, which happens in the
            # PREFILL pool — the router re-plans the entry with that
            # pool's geometry and requeues it there
            self.on_preempt_requeue(slot.tenant, pending)
        else:
            self._queue.requeue_front(slot.tenant, pending)
        self.preemptions[slot.tenant] = \
            self.preemptions.get(slot.tenant, 0) + 1
        slot._clear()
        slot.state = "free"

    def _return_window(self, slot: _Slot) -> None:
        """A cache by layer kind: ``slot``'s request is over here (done, or
        preempted), and the window kind's pages it still holds go back with
        the full kind's."""
        if not self._kinds:
            return
        self.window_allocator.reclaim(slot.window_blocks[::-1])
        self.kv_kind_blocks["full", "returned"] += len(slot.blocks)
        self.kv_kind_blocks["window", "returned"] += len(slot.window_blocks)

    def _dispatch(self, fn, *args):
        """Every device burst charges through the guard when one is
        attached — acquire, SYNC, charge measured wall time (the same
        token-gated shape as the run-to-completion serving path).  The
        sync is GUARD-ONLY: an unguarded engine leaves the dispatch
        asynchronous, so host-side work (admission, the caller's
        arrival loop) overlaps device execution, and emitted tokens
        are read one step later in :meth:`_consume_inflight`."""
        plan, self._launching = self._launching, None
        if self._marshal is not None:
            self._marshal.__exit__(None, None, None)
            self._marshal = None
        if self.fault_clock is not None:
            # chaos seam: an injected slow/hung dispatch advances the
            # fault clock's virtual time here, where the fleet's
            # dispatch watchdog measures
            self.fault_clock.on_dispatch(self)
        if self.guard is None:
            return self._launch(plan, fn, args)[0]
        entered = time.monotonic()
        self.guard.acquire()
        start = time.monotonic()
        try:
            out, launch = self._launch(plan, fn, args)
            with profiling.span("kubeshare.engine.device_wait") as wait:
                out = jax.block_until_ready(out)
        finally:
            elapsed_ms = (time.monotonic() - start) * 1e3
            self.guard.charge(elapsed_ms)
        total = wait.end - entered
        if total > SLOW_DISPATCH_S and \
                total * 1e3 > SLOW_DISPATCH_FACTOR * self._dispatch_estimate_ms:
            self._report_slow_dispatch(entered, start, launch, wait)
        self._dispatch_estimate_ms = (0.8 * self._dispatch_estimate_ms
                                      + 0.2 * elapsed_ms)
        return out

    def _begin_launch(self, plan: _StepPlan) -> None:
        """Argument marshalling for ``plan`` starts: the
        ``kubeshare.engine.marshal`` span runs from here to
        :meth:`_dispatch`'s entry, which also takes the plan for the
        launch span's attributes and the request's prefill stamps."""
        self._launching = plan
        self._marshal = profiling.span("kubeshare.engine.marshal")
        self._marshal.__enter__()

    def _launch(self, plan: Optional[_StepPlan], fn, args):
        """The call of one step program until it returns (the enqueue;
        on an unguarded engine nothing waits for the device here);
        returns its result and the launch's span.  A dispatch outside
        any plan is a single-block pool write: a tier promotion's
        upload or a copy-on-write."""
        if plan is None:
            kind = "upload" if fn is self._upload_step else "copy"
            attrs = {"kind": kind, "lanes": 0, "rows": 0, "chunk": 0,
                     "attend": "", "program": stages.program_name(kind)}
        else:
            # the furthest row any lane holds once the dispatch's first
            # rows are written: a decode lane's length and its new row,
            # the chunk's end
            step_rows = self._diffusion or 1  # a lane's new rows
            reach = max([s.length + step_rows for s in plan.decode_slots]
                        + [sum(plan.chunk[:2]) if plan.chunk else 0])
            attrs = {"kind": plan.kind, "lanes": len(plan.decode_slots),
                     "rows": sum(s.length for s in plan.decode_slots),
                     "chunk": plan.chunk[1] if plan.chunk else 0,
                     "attend": self._attend_of(plan),
                     "program": self._program_of(plan)}
            if self._kinds:
                # what a window layer reads of the lanes' rows
                window = self.model_config.attention_window
                attrs["window_rows"] = sum(
                    min(s.length, window) for s in plan.decode_slots)
            passes = self._weight_passes(plan)
            if passes is not None:
                attrs["weight_passes"] = passes
                self._count_weight_passes(plan.kind, passes)
            if self.model_config.routed:
                # every block kind's last layer is an expert layer; the
                # widest pass over it: the lanes' rows, or the chunk's, or
                # both side by side in a fused mixed dispatch's first step
                lane_rows = (len(self._slots) * step_rows
                             * bool(attrs["lanes"]))
                attrs["experts"] = experts_path(
                    self.params["layers"][-1]["moe"],
                    lane_rows + attrs["chunk"]
                    if plan.kind == "mixed" and self._mixed_fused
                    else max(lane_rows, attrs["chunk"]))
            self.view_rows_held += attrs["rows"]
            # whole key blocks, which divide the view
            self.view_rows_reached += (
                -(-reach // self._key_block_rows) * self._key_block_rows)
            self.view_rows_configured += (
                self._table_width * self.engine_config.block_size)
        self._launch_carried = [0, 0]
        with profiling.span("kubeshare.engine.launch", **attrs) as launch:
            out = fn(*args)
            # what the program's call carried over (one packed buffer):
            # known once it has been called
            host_args, host_bytes = self._launch_carried
            launch.set(host_args=host_args, host_bytes=host_bytes)
        self.host_arg_transfers += host_args
        self.host_arg_bytes += host_bytes
        if plan is not None and plan.prefill_slot is not None:
            result = plan.prefill_slot.result
            result.prefill_chunks += 1
            if result.first_dispatch_at is None:
                result.first_dispatch_at = launch.start
        return out, launch

    def _program_of(self, plan: _StepPlan) -> str:
        """The name :meth:`warmup` registered ``plan``'s step program
        under (``serving/stages.py``): its kind and the widths that pick
        the compiled shape."""
        widths = [plan.chunk[1]] if plan.chunk else []
        if plan.kind in ("verify", "mixed_verify"):
            widths.append(plan.verify_width)
        elif plan.kind in ("loop", "spec_loop"):
            widths.append(self._loop_k)
        return stages.program_name(plan.kind, *widths)

    def _weight_passes(self, plan: _StepPlan) -> Optional[int]:
        """The passes over the layer stack ``plan``'s program makes: a
        chunk's (prefill, verify, a diffusion pass) 1, a span's
        ``decode_span``, and a mixed dispatch's the span's alone where the
        chunk rides its first (``paged.mixed_weight_passes``), else the
        two parts' sum.  None for a device-resident loop, whose units are
        data: counted when it is consumed."""
        span = self.engine_config.decode_span
        if plan.kind in ("loop", "spec_loop"):
            return None
        if plan.kind == "mixed":
            return self._mixed_passes
        if plan.kind.startswith("mixed"):
            return 2
        return span if plan.kind == "decode" else 1

    def _note_carried(self, count: int, nbytes: int) -> None:
        """A step program's call took ``count`` host arrays of ``nbytes``
        in all over to the device (``packed_args.PackedProgram``)."""
        self._launch_carried[0] += count
        self._launch_carried[1] += nbytes

    def _count_weight_passes(self, kind: str, passes: int) -> None:
        self.weight_passes[kind] = self.weight_passes.get(kind, 0) + passes

    def _attend_of(self, plan: _StepPlan) -> str:
        """What ``plan``'s decode lanes' attention runs ("kernel",
        "blocks" or "whole": ``paged.attend_path``, which the step
        programs choose by); the chunk's, where no lane decodes."""
        if plan.kind in ("verify", "mixed_verify"):
            query_rows = plan.verify_width
        elif plan.decode_slots:
            # a span is so many one-row steps; a diffusion pass a block
            query_rows = self._diffusion or 1
        else:
            query_rows = plan.chunk[1]
        config = self.model_config
        # the width of a head AS THE POOL HOLDS IT: narrower heads lie
        # paired in a row (kv_blocks.KVRowLayout heads_paired).  A cache by
        # layer kind: each kind's own arrays, and both paths ("full+window")
        # where they differ
        paths = [attend_path(config.block, query_rows, self._table_width,
                             k, v, k.shape[-1], config.diffusion_block)
                 for k, v in zip(jax.tree_util.tree_leaves(self.pool.k),
                                 jax.tree_util.tree_leaves(self.pool.v))]
        return "+".join(dict.fromkeys(paths))

    def _report_slow_dispatch(self, entered: float, start: float,
                              launch: profiling.span,
                              wait: profiling.span) -> None:
        """One WARNING for a gated dispatch that lasted seconds: which
        program it was, what it carried and where the time went (whether
        the guard went to the broker, and in how many round trips, is on
        its own spans, in the ring)."""
        me = threading.current_thread().name

        def last_of(name: str) -> Dict:
            mine = [r for r in profiling.spans(since=entered, name=name)
                    if r[3] == me]
            return mine[-1][4] if mine else {}

        acquired = last_of("kubeshare.guard.acquire")
        trips = last_of("kubeshare.client.acquire").get("round_trips")
        seconds = {"acquire": start - entered, "launch": launch.seconds,
                   "device_wait": wait.seconds}
        self.slow_dispatches[max(seconds, key=seconds.get)] += 1
        self.log.warning(
            "slow dispatch: %.3f s against a running estimate of %.1f ms; "
            "program=%s kind=%s lanes=%d chunk=%d experts=%s "
            "guard.acquire=%.3f s (%s) launch=%.3f s device_wait=%.3f s",
            wait.end - entered, self._dispatch_estimate_ms,
            launch.attrs["program"], launch.attrs["kind"],
            launch.attrs["lanes"], launch.attrs["chunk"],
            launch.attrs.get("experts", "none"),
            seconds["acquire"],
            "unnamed guard" if not acquired else
            "held" if not acquired.get("broker") else
            "broker" if trips is None else f"broker, {trips} round trips",
            seconds["launch"], seconds["device_wait"])

    def _next_prefill_slot(self, prefill: List[_Slot]) -> _Slot:
        """Round-robin over filling slots: the prefill slot at or
        after the rotating pointer goes next, so a many-chunk prompt
        shares prefill ticks with later admissions instead of
        monopolizing them (the old ``prefill[0]`` head-of-line bug)."""
        chosen = min(prefill, key=lambda s:
                     (s.idx - self._prefill_rr) % len(self._slots))
        self._prefill_rr = (chosen.idx + 1) % len(self._slots)
        return chosen

    def _sliced_chunk(self, slot: _Slot) -> Tuple[int, int, int]:
        """Pop the slot's next prefill chunk for a mixed dispatch,
        sliced to the fused budget: a wider chunk yields its leading
        largest-power-of-two piece <= budget and the remainder
        re-enters the plan head as POWER-OF-TWO chunks (binary
        decomposition, widest first).  Every piece — dispatched fused
        OR standalone, should the decode pool drain mid-slice — is an
        already-warmed bucket width, so slicing never compiles a new
        shape (review regression: a raw ``width - piece`` remainder is
        not a bucket width).  A pad-forward chunk (its logits row
        inside the chunk, not at its end) cannot be split around its
        logits row and is returned whole."""
        start, width, last_row = slot.plan.pop(0)
        budget = self._mixed_budget
        if width <= budget or last_row != width - 1:
            return (start, width, last_row)
        piece = 1 << (budget.bit_length() - 1)
        rest, offset, rem = [], start + piece, width - piece
        while rem:
            w = 1 << (rem.bit_length() - 1)
            rest.append((offset, w, w - 1))
            offset += w
            rem -= w
        slot.plan[:0] = rest
        return (start, piece, piece - 1)

    def _prefill_lane(self, slot: _Slot, chunk: Tuple[int, int, int]):
        """Device arguments for one slot's prefill chunk — shared by
        the standalone and the mixed dispatch, so both run the exact
        same lane."""
        start, width, last_row = chunk
        final = not slot.plan
        segment = slot.prompt[start: start + width]
        if segment.size < width:  # short-prompt pad tail (dead rows)
            segment = np.pad(segment, (0, width - segment.size))
        # a 'retention' lane's table changes while the request lives (a
        # fold zeroes the entries behind it in .consume, which does not
        # wait for a chunk that yields no token; so does a window kind's):
        # the dispatch gets a copy of it, not a view the backend may still
        # be reading
        table = slot.table[None].copy() if self._retention or self._kinds \
            else slot.table[None]
        return (final, table,
                np.asarray([start], np.int32),
                np.asarray(segment[None], np.int32),
                np.asarray([last_row], np.int32),
                # the pick is consumed only on the prompt's final chunk
                np.asarray([slot.temperature if final else 0.0],
                           np.float32),
                np.asarray(slot.first_key if final else
                           np.zeros(2, np.uint32))[None])

    def _recurrent_args(self, p_slot: Optional[_Slot],
                        decode_slots: Optional[List[_Slot]]) -> tuple:
        """The last arguments of a dispatch of an engine whose lanes hold a
        state by slot: the states (and a 'retention' block's gate array),
        donated, then the chunk's lane's fold point and slot, then the
        decode lanes' fold points by slot (zeros where nothing folds: the
        short convolutions).  Another engine passes nothing more."""
        if not self._stateful:
            return ()
        args = [Recurrent(self.pool.gate, self.states)]
        if p_slot is not None:
            args += [np.asarray([p_slot.folded], np.int32),
                     np.asarray([p_slot.idx], np.int32)]
        if decode_slots is not None:
            folded = np.zeros((self.engine_config.num_slots,), np.int32)
            for slot in decode_slots:
                folded[slot.idx] = slot.folded
            args.append(folded)
        return tuple(args)

    def _keep_cache(self, pk, pv, rest: list) -> list:
        """The pool a dispatch returned (and the states by slot with a
        'retention' engine's gate array, which come last) back onto the
        engine; returns what else the dispatch returned (a routed block's
        counts)."""
        if self._stateful:
            gate, self.states = rest.pop()  # (gate, a state a layer)
            self.pool = replace(self.pool, k=pk, v=pv, gate=gate)
        else:
            self.pool = replace(self.pool, k=pk, v=pv)
        return rest

    def _note_retention(self, p_slot: Optional[_Slot],
                        chunk: Optional[Tuple[int, int, int]],
                        decode_slots: List[_Slot]) -> None:
        """What a 'retention' dispatch carries, for :meth:`_observe_retention`:
        the lanes, those that read a state, and the unfolded rows read
        (a decode lane's tail once a step, one row longer each)."""
        if not self._retention:
            return
        span = self.engine_config.decode_span
        tails = [s.length - s.folded for s in decode_slots]
        note = {
            "lanes": len(decode_slots) + (p_slot is not None),
            "state_lanes": sum(1 for s in decode_slots if s.folded),
            "passes": span if decode_slots else 0,
            "tail_rows": sum(span * t + span * (span + 1) // 2
                             for t in tails),
            "chunk": chunk[1] if chunk else 0, "chunk_state": 0,
            "decode_slots": [(s, s.rid) for s in decode_slots],
            "prefill": None,
        }
        if p_slot is not None:
            start, _, last_row = chunk
            note["chunk_state"] = int(p_slot.folded > 0)
            note["tail_rows"] += start + last_row + 1 - p_slot.folded
            note["prefill"] = (p_slot, p_slot.rid, start + last_row + 1)
        self._retention_inflight = note

    def _note_conv(self, p_slot: Optional[_Slot],
                   chunk: Optional[Tuple[int, int, int]],
                   decode_slots: List[_Slot]) -> None:
        """What a dispatch of a model with short convolutions carries, for
        :meth:`_observe_conv`: every live lane reads its slot's state once
        a pass and convolution layer — but a chunk that begins at row 0,
        which reads zeros (a reset)."""
        if not self._conv:
            return
        span = self.engine_config.decode_span
        reset = int(chunk is not None and chunk[0] == 0)
        passes = span if decode_slots else 0
        self._conv_inflight = {
            "lanes": len(decode_slots) + (p_slot is not None),
            "passes": passes, "resets": reset,
            "chunk": chunk[1] if chunk else 0,
            "state_reads": self.model_config.conv_layers * (
                len(decode_slots) * passes
                + int(p_slot is not None) - reset)}

    def _observe_conv(self) -> None:
        """One such dispatch into the counters and a
        ``kubeshare.engine.conv`` span (its attributes are what a trace's
        reader can reach)."""
        note, self._conv_inflight = self._conv_inflight, None
        with profiling.span("kubeshare.engine.conv", **note):
            self.conv_state_reads += note["state_reads"]
            self.conv_state_resets += note["resets"]

    def _decode_lanes(self, decode_slots: List[_Slot],
                      n_steps: Optional[int] = None):
        """Device arguments for a decode span over the slot pool —
        shared by the standalone, the mixed, and (with ``n_steps`` =
        K*span) the device-loop dispatch.  The key window is sliced
        flat: a K-unit loop consumes exactly the keys K back-to-back
        span dispatches would, at the same emission indices.  They are
        numpy arrays and stay so: the step program lays them into the
        one buffer its call carries to the device (``packed_args``; as
        :meth:`warmup`'s does, so a program has one signature); a
        ``jnp.asarray`` each was most of the host's time a dispatch,
        and a transfer each most of the gate's."""
        ec = self.engine_config
        s = ec.num_slots
        steps = ec.decode_span if n_steps is None else n_steps
        tables = np.zeros((s, self._table_entries), np.int32)
        lengths = np.zeros((s,), np.int32)
        active = np.zeros((s,), bool)
        tokens = np.zeros((s,), np.int32)
        temps = np.zeros((s,), np.float32)
        keys = np.zeros((s, steps, 2), np.uint32)
        budgets = np.zeros((s,), np.int32)
        for slot in decode_slots:
            i = slot.idx
            tables[i] = slot.table
            lengths[i] = slot.length
            active[i] = True
            tokens[i] = slot.generated[-1]
            temps[i] = slot.temperature
            budgets[i] = slot.max_new - len(slot.generated)
            if slot.temperature > 0.0:
                # this span consumes the request's next step keys in the
                # exact dense-split order
                offset = len(slot.generated) - 1
                window = slot.step_keys[offset: offset + steps]
                keys[i, : len(window)] = window
        return tables, lengths, active, tokens, temps, keys, budgets

    def _charge_collectives(self, family: str, kind: str, *, lanes: int,
                            chunk: int = 0, span: int = 0,
                            width: int = 0) -> None:
        """Account one sharded dispatch's estimated collective traffic
        (no-op on a single-device engine — the counters stay zero)."""
        if self._sharded is None:
            return
        self.collective_bytes[family] += \
            self._sharded.dispatch_collective_bytes(
                kind, lanes=lanes, chunk=chunk, span=span, width=width,
                view_rows=self._table_width * self.engine_config.block_size)

    def _run_prefill_chunk(self, slot: _Slot,
                           chunk: Optional[Tuple[int, int, int]] = None
                           ) -> None:
        # ONE lane per prefill dispatch: chunks are already MXU-shaped
        # [width, d] work, so batching lanes buys nothing compute-wise —
        # and a static multi-lane shape would bill every dispatch for
        # its padded lanes (~2x in an earlier round's CPU timing; not
        # measured on the chip).  The first-token pick rides fused in the
        # same dispatch.
        if chunk is None:
            chunk = slot.plan.pop(0)
        final, table, start, segment, last_row, temp, key = \
            self._prefill_lane(slot, chunk)
        if self._diffusion:
            # whole blocks of the prompt: no token comes of them
            picked = None
            pk, pv, *counts = self._dispatch(
                self._prefill_step, self.params, self.pool.k, self.pool.v,
                table, start, np.ones((1,), bool), segment, last_row)
        else:
            self._note_retention(slot, chunk, [])
            self._note_conv(slot, chunk, [])
            picked, pk, pv, *counts = self._dispatch(
                self._prefill_step, self.params, self.pool.k, self.pool.v,
                table, start, np.ones((1,), bool), segment, last_row,
                temp, key, *self._recurrent_args(slot, None))
        counts = self._keep_cache(pk, pv, counts)
        self._routing_inflight = (counts, segment.shape[1], 1)
        self.prefill_chunks += 1
        self._charge_collectives("prefill_chunk", "prefill", lanes=1,
                                 chunk=segment.shape[1])
        # fair-share service: the prefill width actually dispatched (a
        # prefix-cache hit charges only its uncached suffix — tokend's
        # charge-measured-work principle)
        self._queue.charge(slot.tenant, chunk[1])
        # the fused pick at the final chunk's last-real-row logits IS
        # the first token; read when consumed (one step later), with
        # a routed block's counts
        if final or counts or self._stateful or self._kinds:
            self._inflight = ("diffusion" if self._diffusion else "span",
                              None, (slot, picked) if final else None)

    def _run_decode_step(self, decode_slots: List[_Slot]) -> None:
        tables, lengths, active, tokens, temps, keys, budgets = \
            self._decode_lanes(decode_slots)
        self._note_retention(None, None, decode_slots)
        self._note_conv(None, None, decode_slots)
        emitted, pk, pv, *counts = self._dispatch(
            self._decode_step, self.params, self.pool.k, self.pool.v,
            tables, lengths, active, tokens, temps, keys, budgets,
            *self._recurrent_args(None, decode_slots))
        counts = self._keep_cache(pk, pv, counts)
        span = self.engine_config.decode_span
        self._routing_inflight = (counts, len(tokens) * span, span)
        self.decode_steps += 1
        self._charge_collectives(
            "decode_span", "decode", lanes=self.engine_config.num_slots,
            span=self.engine_config.decode_span)
        self._inflight = ("span", (emitted, list(decode_slots), budgets),
                          None)

    def _run_loop_step(self, decode_slots: List[_Slot]) -> None:
        """Launch the device-resident multi-step loop: up to
        ``steps_per_launch`` span-units in ONE dispatch.  The ring and
        the units-ran scalar stay on device until consumed — reading
        ``units`` here would force a sync and break the one-step-ahead
        pipeline, so ALL unit-proportional bookkeeping (decode_steps,
        loop_units, collective byte charges) is deferred to
        :meth:`_consume_inflight`."""
        ec = self.engine_config
        # the EFFECTIVE depth — the autotuner may have lowered it below
        # the configured ceiling; every reachable depth is a warmed
        # shape, so the selection never compiles
        k_depth = self._loop_k
        n_steps = k_depth * ec.decode_span
        tables, lengths, active, tokens, temps, keys, budgets = \
            self._decode_lanes(decode_slots, n_steps)
        ring, units, pk, pv = self._dispatch(
            self._loop_steps[k_depth], self.params, self.pool.k,
            self.pool.v,
            tables, lengths, active, tokens, temps, keys, budgets)
        self.pool = replace(self.pool, k=pk, v=pv)
        self.loop_launches += 1
        self._inflight = ("loop", (ring, units, list(decode_slots),
                                   budgets), None)

    def _spec_loop_lanes(self, decode_slots: List[_Slot],
                         k_depth: int):
        """Device arguments for a speculative loop launch: the decode-
        lane marshal plus each lane's right-aligned on-device drafting
        window and the FLAT key buffer K verify units consume (unit u
        reads key indices ``done .. done+W-1`` where ``done`` is the
        lane's in-loop emission count — exactly the indices K separate
        verify dispatches would have consumed)."""
        ec = self.engine_config
        s = ec.num_slots
        n_keys = k_depth * (1 + ec.draft_len)
        tables = np.zeros((s, self._table_entries), np.int32)
        lengths = np.zeros((s,), np.int32)
        active = np.zeros((s,), bool)
        tokens = np.zeros((s,), np.int32)
        temps = np.zeros((s,), np.float32)
        keys = np.zeros((s, n_keys, 2), np.uint32)
        budgets = np.zeros((s,), np.int32)
        hist = np.zeros((s, SPEC_LOOP_HIST), np.int32)
        hist_len = np.zeros((s,), np.int32)
        dcaps = np.zeros((s,), np.int32)
        for slot in decode_slots:
            i = slot.idx
            tables[i] = slot.table
            lengths[i] = slot.length
            active[i] = True
            tokens[i] = slot.generated[-1]
            temps[i] = slot.temperature
            budgets[i] = slot.max_new - len(slot.generated)
            if slot.temperature > 0.0:
                offset = len(slot.generated) - 1
                window = slot.step_keys[offset: offset + n_keys]
                keys[i, : len(window)] = window
            toks = (list(slot.prompt)
                    + list(slot.generated))[-SPEC_LOOP_HIST:]
            hist[i, SPEC_LOOP_HIST - len(toks):] = toks
            hist_len[i] = len(toks)
            dcaps[i] = min(slot.draft_width, self._loop_draft_cap)
        return (tables, lengths, active, tokens, temps, keys, budgets,
                hist, hist_len, dcaps)

    def _ring_lanes(self, k_depth: int):
        """Pre-marshaled pending-lane ring arrays from the staged
        admissions (rows past the returned count are zero and never
        read — the device guards activation on ``head < ring_count``).
        Returns the arrays plus the staged slots they were built from,
        in ring order."""
        ec = self.engine_config
        r = ec.admission_ring
        n_keys = k_depth * (1 + ec.draft_len)
        r_tables = np.zeros((r, self._table_width), np.int32)
        r_lengths = np.zeros((r,), np.int32)
        r_tokens = np.zeros((r,), np.int32)
        r_temps = np.zeros((r,), np.float32)
        r_keys = np.zeros((r, n_keys, 2), np.uint32)
        r_budgets = np.zeros((r,), np.int32)
        r_hist = np.zeros((r, SPEC_LOOP_HIST), np.int32)
        r_hist_len = np.zeros((r,), np.int32)
        r_caps = np.zeros((r,), np.int32)
        staged = list(self._ring_staged[:r])
        for j, slot in enumerate(staged):
            r_tables[j] = slot.table
            r_lengths[j] = slot.length
            r_tokens[j] = slot.generated[-1]
            r_temps[j] = slot.temperature
            r_budgets[j] = slot.max_new - len(slot.generated)
            if slot.temperature > 0.0:
                offset = len(slot.generated) - 1
                window = slot.step_keys[offset: offset + n_keys]
                r_keys[j, : len(window)] = window
            toks = (list(slot.prompt)
                    + list(slot.generated))[-SPEC_LOOP_HIST:]
            r_hist[j, SPEC_LOOP_HIST - len(toks):] = toks
            r_hist_len[j] = len(toks)
            r_caps[j] = min(slot.draft_width, self._loop_draft_cap)
        return (r_tables, r_lengths, r_tokens, r_temps, r_keys,
                r_budgets, r_hist, r_hist_len, r_caps, staged)

    def _fill_admission_ring(self) -> None:
        """Top the pending-lane ring up from the queue.  Each staged
        entry runs the FULL admission path (fair order, quota, prefix
        cache, reservation) into a detached ``_Slot``, then prefills
        its prompt synchronously through the warmed standalone chunk
        shapes — by launch time it is indistinguishable from a lane
        that finished prefill in an engine slot, minus the lane
        binding (the device performs that at a span boundary; _admit
        does it host-side if the loop never activates the entry).

        Ring fill never preempts: staging a pending lane is not worth
        evicting a running one.  It never touches ``_inflight`` either
        — the pipelined step may hold a dispatch whose effects are
        still unconsumed."""
        ec = self.engine_config
        room = ec.admission_ring - len(self._ring_staged)
        while room > 0:
            if self.admission_gate is not None \
                    and not self.admission_gate():
                return
            staged = None
            for tenant in self._queue.order():
                spec = self.tenants.get(tenant)
                pending = self._queue.peek(tenant)
                if self._quota_blocked(pending, spec):
                    continue
                cand = _Slot(-1, self._table_width)
                outcome = self._try_admit(pending, spec, cand)
                if outcome == "admitted":
                    self._queue.pop(tenant)
                    staged = cand
                    break
                if outcome == "quota":
                    continue
                return  # pool exhausted
            if staged is None:
                return
            while staged.plan:
                chunk = staged.plan.pop(0)
                self._begin_launch(_StepPlan(
                    "prefill", prefill_slot=staged, chunk=chunk))
                final, table, start, segment, last_row, temp, key = \
                    self._prefill_lane(staged, chunk)
                picked, pk, pv = self._dispatch(
                    self._prefill_step, self.params, self.pool.k,
                    self.pool.v, table, start, np.ones((1,), bool),
                    segment, last_row, temp, key)
                self.pool = replace(self.pool, k=pk, v=pv)
                self.prefill_chunks += 1
                self._charge_collectives(
                    "prefill_chunk", "prefill", lanes=1,
                    chunk=segment.shape[1])
                self._queue.charge(staged.tenant, chunk[1])
                if final:
                    self._finish_prefill(
                        staged, int(np.asarray(picked)[0]))
            if staged.state == "decode":
                self._ring_staged.append(staged)
                room -= 1
            # a request already done at its first token (max_new == 1
            # or instant EOS) retired inside _finish_prefill and never
            # stages — the loop continues with the queue advanced

    def _run_spec_loop_step(self, plan: _StepPlan) -> None:
        """Launch the SPECULATIVE device loop (device residency v2):
        up to K draft-verify-accept units — plus ring admissions at
        span boundaries — in ONE dispatch.  Like :meth:`_run_loop_step`
        all unit-proportional bookkeeping defers to
        :meth:`_consume_inflight`; the host draft that armed this plan
        is discarded (the device re-drafts every unit itself from its
        on-device history windows — scheduling-only, see
        :meth:`_plan_decode_phase`)."""
        k_depth = self._loop_k
        if self.engine_config.admission_ring:
            self._fill_admission_ring()
        decode_slots = plan.decode_slots
        (tables, lengths, active, tokens, temps, keys, budgets, hist,
         hist_len, dcaps) = self._spec_loop_lanes(decode_slots, k_depth)
        (r_tables, r_lengths, r_tokens, r_temps, r_keys, r_budgets,
         r_hist, r_hist_len, r_caps, staged) = self._ring_lanes(k_depth)
        out_p, out_a, out_d, units, head, pk, pv = self._dispatch(
            self._spec_loops[k_depth], self.params, self.pool.k,
            self.pool.v,
            tables, lengths, active, tokens, temps, keys, budgets, hist,
            hist_len, dcaps, r_tables, r_lengths, r_tokens, r_temps,
            r_keys, r_budgets, r_hist, r_hist_len, r_caps,
            np.asarray(len(staged), np.int32))
        self.pool = replace(self.pool, k=pk, v=pv)
        self.spec_loop_launches += 1
        self._inflight = ("spec_loop",
                          (out_p, out_a, out_d, units, head,
                           list(decode_slots), staged), None)

    def _run_mixed_step(self, decode_slots: List[_Slot], p_slot: _Slot,
                        chunk: Tuple[int, int, int]) -> None:
        """The stall-free fused dispatch: every decode lane advances
        its span AND ``p_slot`` consumes one budget-bounded prefill
        chunk, in ONE device program (``paged.paged_mixed_step``)."""
        final, table, start, segment, last_row, temp, key = \
            self._prefill_lane(p_slot, chunk)
        tables, lengths, active, tokens, temps, keys, budgets = \
            self._decode_lanes(decode_slots)
        self._note_retention(p_slot, chunk, decode_slots)
        self._note_conv(p_slot, chunk, decode_slots)
        picked, emitted, pk, pv, *counts = self._dispatch(
            self._mixed_step, self.params, self.pool.k, self.pool.v,
            table, start, segment, last_row, temp, key,
            tables, lengths, active, tokens, temps, keys, budgets,
            *self._recurrent_args(p_slot, decode_slots))
        counts = self._keep_cache(pk, pv, counts)
        span = self.engine_config.decode_span
        self._routing_inflight = (
            counts, segment.shape[1] + len(tokens) * span,
            self._mixed_passes)
        self.prefill_chunks += 1
        self.decode_steps += 1
        self.mixed_steps += 1
        self._charge_collectives("prefill_chunk", "prefill", lanes=1,
                                 chunk=segment.shape[1])
        self._charge_collectives(
            "decode_span", "decode", lanes=self.engine_config.num_slots,
            span=self.engine_config.decode_span)
        self._queue.charge(p_slot.tenant, chunk[1])
        self._inflight = ("span", (emitted, list(decode_slots), budgets),
                          (p_slot, picked) if final else None)

    def _diffusion_lanes(self, decode_slots: List[_Slot]):
        """Device arguments for one pass over the lanes' blocks: each
        lane's table, its cached length (where its block begins), the
        block's known tokens, which rows are still masked, which of them
        may be committed, and how many this pass commits."""
        s, b = self.engine_config.num_slots, self._diffusion
        tables = np.zeros((s, self._table_entries), np.int32)
        lengths = np.zeros((s,), np.int32)
        active = np.zeros((s,), bool)
        tokens = np.zeros((s, b), np.int32)
        masked = np.zeros((s, b), bool)
        open_rows = np.zeros((s, b), bool)
        quota = np.zeros((s,), np.int32)
        for slot in decode_slots:
            i, block = slot.idx, slot.block
            tables[i] = slot.table
            lengths[i] = slot.length
            active[i] = True
            tokens[i] = block.tokens
            masked[i] = block.masked
            open_rows[i] = block.open
            # a finished block (the commit pass) has had every step
            if block.step < len(self._transfer):
                quota[i] = self._transfer[block.step]
        return tables, lengths, active, tokens, masked, open_rows, quota

    def _run_diffusion_step(self, plan: _StepPlan) -> None:
        """One pass over every decode lane's block — for each lane the
        denoising pass its block is at, or the commit pass over a
        finished one (``paged.paged_diffusion_pass``) — and, in the mixed
        flavour, one block-causal prefill chunk for the filling slot in
        the same program.  It counts once in ``decode_steps``, and in
        ``prefill_chunks`` and ``mixed_steps`` when it carries a chunk."""
        lanes = self._diffusion_lanes(plan.decode_slots)
        p_slot, final, chunk_rows = plan.prefill_slot, False, 0
        if p_slot is not None:
            final, table, start, segment, last_row, _, _ = \
                self._prefill_lane(p_slot, plan.chunk)
            chunk_rows = segment.shape[1]
            picked, commit, pk, pv, *counts = self._dispatch(
                self._mixed_diffusion_step, self.params, self.pool.k,
                self.pool.v, table, start, segment, last_row, *lanes)
            self.prefill_chunks += 1
            self.mixed_steps += 1
            self._queue.charge(p_slot.tenant, plan.chunk[1])
        else:
            picked, commit, pk, pv, *counts = self._dispatch(
                self._diffusion_step, self.params, self.pool.k,
                self.pool.v, *lanes)
        self.pool = replace(self.pool, k=pk, v=pv)
        self.decode_steps += 1
        self._routing_inflight = (
            counts, chunk_rows + lanes[3].size, 1 + (p_slot is not None))
        self._inflight = ("diffusion",
                          (picked, commit, list(plan.decode_slots),
                           chunk_rows),
                          (p_slot, None) if final else None)

    def _verify_lanes(self, decode_slots: List[_Slot],
                      drafts: Dict[int, List[int]], width: int):
        """Device arguments for a verify chunk over the slot pool.
        Proposal columns a lane does not fill carry ``-1`` — an
        impossible token, so the acceptance cumprod can never count a
        pad as a match.  Each lane's key window is the SAME
        ``step_keys[offset : offset + width]`` slice a width-``width``
        decode span would consume: accepted picks burn their keys at
        the identical emission indices, and a rejected column's key is
        simply re-consumed at the same emission number next round —
        the schedule stays aligned with the non-speculative stream by
        construction."""
        s = self.engine_config.num_slots
        tables = np.zeros((s, self._table_entries), np.int32)
        lengths = np.zeros((s,), np.int32)
        active = np.zeros((s,), bool)
        tokens = np.full((s, width), -1, np.int32)
        tokens[:, 0] = 0
        widths = np.ones((s,), np.int32)
        temps = np.zeros((s,), np.float32)
        keys = np.zeros((s, width, 2), np.uint32)
        budgets = np.zeros((s,), np.int32)
        k_lanes = np.zeros((s,), np.int32)
        for slot in decode_slots:
            i = slot.idx
            tables[i] = slot.table
            lengths[i] = slot.length
            active[i] = True
            tokens[i, 0] = slot.generated[-1]
            prop = drafts.get(i, [])
            k_lanes[i] = len(prop)
            widths[i] = 1 + len(prop)
            if prop:
                tokens[i, 1: 1 + len(prop)] = prop
            temps[i] = slot.temperature
            budgets[i] = slot.max_new - len(slot.generated)
            if slot.temperature > 0.0:
                offset = len(slot.generated) - 1
                window = slot.step_keys[offset: offset + width]
                keys[i, : len(window)] = window
        return (tables, lengths, active, tokens, widths, temps, keys,
                budgets, k_lanes)

    def _run_verify_step(self, plan: _StepPlan) -> None:
        """One draft-verify chunk: every decode lane scores its
        proposal row (width-1 lanes degenerate to a decode step) in
        ONE cached dispatch (``paged.paged_verify_span``)."""
        (tables, lengths, active, tokens, widths, temps, keys, budgets,
         k_lanes) = self._verify_lanes(
            plan.decode_slots, plan.drafts, plan.verify_width)
        picked, accepts, pk, pv = self._dispatch(
            self._verify_step, self.params, self.pool.k, self.pool.v,
            tables, lengths, active, tokens, widths, temps, keys)
        self.pool = replace(self.pool, k=pk, v=pv)
        self.verify_steps += 1
        self._charge_collectives(
            "verify_span", "verify", lanes=self.engine_config.num_slots,
            width=plan.verify_width)
        self._inflight = ("verify",
                          (picked, accepts, list(plan.decode_slots),
                           k_lanes, budgets), None)

    def _run_mixed_verify_step(self, plan: _StepPlan) -> None:
        """The speculative flavor of the stall-free fused dispatch:
        every decode lane rides one verify chunk AND the filling slot
        consumes one budget-bounded prefill chunk, in ONE device
        program (``paged.paged_mixed_verify_step``)."""
        p_slot, chunk = plan.prefill_slot, plan.chunk
        final, table, start, segment, last_row, temp, key = \
            self._prefill_lane(p_slot, chunk)
        (tables, lengths, active, tokens, widths, temps, keys, budgets,
         k_lanes) = self._verify_lanes(
            plan.decode_slots, plan.drafts, plan.verify_width)
        picked_p, picked, accepts, pk, pv = self._dispatch(
            self._mixed_verify_step, self.params, self.pool.k,
            self.pool.v, table, start, segment, last_row, temp, key,
            tables, lengths, active, tokens, widths, temps, keys)
        self.pool = replace(self.pool, k=pk, v=pv)
        self.prefill_chunks += 1
        self.verify_steps += 1
        self.mixed_verify_steps += 1
        self._charge_collectives("prefill_chunk", "prefill", lanes=1,
                                 chunk=segment.shape[1])
        self._charge_collectives(
            "verify_span", "verify", lanes=self.engine_config.num_slots,
            width=plan.verify_width)
        self._queue.charge(p_slot.tenant, chunk[1])
        self._inflight = ("verify",
                          (picked, accepts, list(plan.decode_slots),
                           k_lanes, budgets),
                          (p_slot, picked_p) if final else None)

    def _consume_inflight(self) -> bool:
        """Apply the previous dispatch's host-side effects: read its
        emitted tokens (the only device sync in the unguarded hot
        loop) and run first-token/acceptance/retirement bookkeeping.
        Runs before every new dispatch and before any scheduling
        decision that needs fresh slot state (preemption, drafting).
        Returns True when there was something to consume."""
        if self._inflight is None:
            return False
        kind, decode_part, prefill_part = self._inflight
        self._inflight = None
        # every device result the bookkeeping below needs, read in one
        # place (on an unguarded engine the first read waits for the
        # device; on a guarded one the dispatch already has)
        with profiling.span("kubeshare.engine.fetch"):
            routing, rows, passes = self._routing_inflight
            # one call: the copies to the host start together
            first, fetched, routing = jax.device_get((
                None if prefill_part is None else prefill_part[1],
                [] if decode_part is None else
                list(decode_part[:_INFLIGHT_DEVICE_ARRAYS[kind]]),
                list(routing)))
            if first is not None:
                first = int(first[0])
        self._routing_inflight = ([], 0, 0)
        if routing:
            self._observe_routing(routing[0], rows, passes)
        if kind == "diffusion":
            if prefill_part is not None:
                self._begin_diffusion(prefill_part[0])
            if decode_part is not None:
                # what the SAME dispatch's routing touched, and the rows
                # of the chunk it carried beside the lanes' blocks
                self._accept_diffusion(
                    decode_part[2], *fetched, chunk=decode_part[3],
                    touched=int(routing[0][3]) if routing else 0)
            return True
        if prefill_part is not None:
            self._finish_prefill(prefill_part[0], first)
        if decode_part is not None:
            if kind == "verify":
                picked, accepts = fetched
                _, _, slots, k_lanes, budgets = decode_part
                self._accept_verify(slots, picked, accepts, k_lanes,
                                    budgets)
            elif kind == "loop":
                # the device loop's epilogue drain: only NOW (the one
                # device sync) is it known how many span-units actually
                # ran, so the unit-proportional counters land here —
                # each unit is one decode_span of work, charged exactly
                # as K=1 span dispatches would have charged it
                ring, units = fetched[0], int(fetched[1])
                _, _, slots, budgets = decode_part
                span = self.engine_config.decode_span
                self.decode_steps += units
                self.loop_units += units
                self._count_weight_passes("loop", units * span)
                self._charge_collectives(
                    "decode_span", "decode",
                    lanes=self.engine_config.num_slots,
                    span=units * span)
                emitted = ring[: units * span]
                # exit reason + realized depth BEFORE acceptance (the
                # acceptance walk retires slots, destroying the lane
                # state the derivation reads)
                self._observe_loop_exit(slots, emitted, budgets, units,
                                        units * span)
                self._accept_decode(slots, emitted, budgets,
                                    n_steps=units * span)
            elif kind == "spec_loop":
                out_p, out_a, out_d, units, head = fetched
                slots, staged = decode_part[5:]
                self._accept_spec_loop(slots, staged, out_p, out_a, out_d,
                                       int(units), int(head))
            else:
                _, slots, budgets = decode_part
                self._accept_decode(slots, fetched[0], budgets)
        if self._retention_inflight is not None:
            self._observe_retention()
        if self._conv_inflight is not None:
            self._observe_conv()
        if self._kinds:
            self._observe_kinds()
        return True

    def _observe_kinds(self) -> None:
        """One dispatch of a cache by layer kind, after its tokens are
        accepted: a lane's next dispatch starts at row ``n`` (its next
        chunk's first row, or its length) and its earliest query reaches
        back to ``n - window + 1``, so the window kind's pages wholly
        behind that row go back to that kind's allocator — the entries
        point at the scratch block from then on, and a table keeps its
        absolute page indices — and the lane draws the pages its next rows
        need: never more than the ``_window_pages`` it was admitted with,
        fewer as the request nears its end; it draws no more than it has
        just released, so the draw cannot fail.  Then the counters and the
        ``kubeshare.engine.kv_kinds`` span (its attributes are what a
        trace's reader can reach)."""
        bs = self.engine_config.block_size
        window = self.model_config.attention_window
        base = self._table_width
        released = drawn = context_rows = 0
        for slot in self._slots:
            if slot.state == "free":
                continue
            rows = slot.plan[0][0] if slot.plan else slot.length
            context_rows += rows
            first = max(rows - window + 1, 0) // bs
            held_from = slot.window_to - len(slot.window_blocks)
            if first > held_from:
                behind = slot.window_blocks[:first - held_from]
                del slot.window_blocks[:first - held_from]
                slot.table[base + held_from:base + first] = 0
                self.window_allocator.reclaim(behind)
                released += len(behind)
            want = min(len(slot.blocks), first + self._window_pages)
            ahead = want - slot.window_to
            if ahead > 0:
                near = self.window_allocator.reserve(
                    ahead, slot.rid, tenant=slot.tenant)
                slot.table[base + slot.window_to:base + want] = near
                slot.window_blocks += near
                slot.window_to = want
                drawn += ahead
        with profiling.span(
                "kubeshare.engine.kv_kinds", released=released, drawn=drawn,
                live_full=self.allocator.blocks_in_use,
                live_window=self.window_allocator.blocks_in_use,
                context_rows=context_rows):
            self.kv_kind_blocks["window", "released"] += released
            self.kv_kind_blocks["window", "drawn"] += drawn

    def _observe_retention(self) -> None:
        """One 'retention' dispatch's bookkeeping, after its tokens are
        accepted: every lane whose tail now holds a key block has folded
        it in the step program's last phase (``paged.fold_lanes``: the
        same arithmetic on the same lengths), so the pages behind the
        fold go back to the allocator here and the lane draws the pages
        its next rows need — never more than its tail's funding, fewer
        as the request nears its end.  Then the counters and the
        ``kubeshare.engine.retention`` span (its attributes are what a
        trace's reader can reach)."""
        note, self._retention_inflight = self._retention_inflight, None
        key_block = paged.KEY_BLOCK
        bs = self.engine_config.block_size
        lanes = [(slot, slot.length) for slot, rid in note["decode_slots"]
                 if slot.rid == rid and slot.state == "decode"]
        if note["prefill"] is not None:
            slot, rid, rows = note["prefill"]
            if slot.rid == rid and slot.state != "free":
                lanes.append((slot, rows))
        folds = freed = 0
        for slot, rows in lanes:
            if rows - slot.folded < key_block:
                continue
            first = slot.folded // bs
            behind = [int(b) for b in slot.table[first:first
                                                 + key_block // bs]]
            slot.table[first:first + key_block // bs] = 0
            for b in behind:
                slot.blocks.remove(b)
            self.allocator.reclaim(behind)
            slot.folded += key_block
            folds += 1
            # the rows this lane may still write: to its request's end,
            # or as far as a tail is funded past the new fold point
            total = self._request_rows(slot.prompt.size, slot.max_new)
            want = self.allocator.blocks_for_tokens(
                min(total, slot.folded + self._tail_rows))
            ahead = want - slot.paged_to
            if ahead > 0:
                spec = self.tenants.get(slot.tenant)
                drawn = self.allocator.reserve(
                    ahead, slot.rid, tenant=spec.name,
                    quota=spec.kv_block_quota)
                slot.table[slot.paged_to:want] = drawn
                slot.blocks += drawn
                slot.paged_to = want
            freed += len(behind) - max(ahead, 0)
        state_reads = (note["state_lanes"] * note["passes"]
                       + note["chunk_state"])
        with profiling.span(
                "kubeshare.engine.retention", lanes=note["lanes"],
                state_lanes=note["state_lanes"], passes=note["passes"],
                state_reads=state_reads, tail_rows=note["tail_rows"],
                folds=folds, folded_rows=folds * key_block,
                pages_freed=freed, chunk=note["chunk"]):
            self.retention_state_reads += state_reads
            self.retention_tail_rows += note["tail_rows"]
            self.retention_folds += folds
            self.retention_pages_freed += freed

    def _observe_routing(self, counts, rows: int, passes: int) -> None:
        """One routed dispatch's counts into the counters and a
        ``kubeshare.engine.routing`` span (its attributes are what a
        trace's reader can reach; its length is this bookkeeping's).
        ``rows`` are the rows the dispatch's passes carried, padded and
        inactive ones too; ``live`` of them chose (``held + zero +
        absent`` is ``top_k`` x expert layers x ``live``)."""
        held, zero, absent, touched, tiles, tile_rows, live = (
            int(c) for c in counts)
        with profiling.span("kubeshare.engine.routing", rows=rows,
                            live=live, passes=passes, held=held, zero=zero,
                            absent=absent, touched=touched, tiles=tiles,
                            tile_rows=tile_rows):
            self.moe_assignments["held"] += held
            self.moe_assignments["zero"] += zero
            self.moe_assignments["absent"] += absent
            self.moe_experts_touched += touched
            self.moe_tiles += tiles
            self.moe_tile_rows += tile_rows
            self.moe_passes += passes

    def _finish_prefill(self, slot: _Slot, first: int) -> None:
        # prompt fully cached: join the decode pool with the fused
        # first-token pick as the stream's head
        slot.length = slot.prompt.size
        slot.generated = [first]
        now = time.monotonic()
        if slot.result.first_token_at is None:
            # a RESUMED slot keeps its original first-token time — TTFT
            # is a property of the request, not of its incarnations
            slot.result.first_token_at = now
            self._observe_ttft(slot.result.ttft, slot.tenant)
        elif slot.last_token_at is not None:
            # resumed after preemption: the stretch from the victim's
            # last pre-preemption token to this one (queue wait +
            # re-prefill) is a REAL inter-token gap — the exact stall
            # the TBT histogram exists to expose
            self._observe_tbt(now - slot.last_token_at, 1, slot.tenant)
        slot.last_token_at = now
        self.tokens_generated += 1
        self.tenant_tokens[slot.tenant] = \
            self.tenant_tokens.get(slot.tenant, 0) + 1
        self._queue.charge(slot.tenant, 1)
        if slot.drafter is not None:
            slot.drafter.extend([first])
        slot.state = "decode"
        ec = self.engine_config
        if (self.on_handoff is not None
                and len(slot.generated) < slot.max_new
                and not (ec.eos_token is not None and first == ec.eos_token)):
            # disagg handoff: the request still has tokens to emit and
            # this pool's role ends at prefill — the router packs the
            # slot's chain and re-admits it into the decode pool.  A
            # request already done (max_new == 1, or first token == EOS)
            # retires here like any monolithic request.
            self.on_handoff(slot)
            self._retire_handoff(slot)
            return
        self._maybe_retire(slot, first)

    def _begin_diffusion(self, slot: _Slot) -> None:
        """The prompt's whole blocks are cached (or there were none): the
        lane joins the decode pool at its first generated block.  No
        token comes of the prefill: the first is served by a pass."""
        slot.length = slot.prompt.size // self._diffusion * self._diffusion
        slot.generated = []
        slot.state = "decode"
        if slot.block is None:  # a resumed lane's rode in with it
            self._open_block(slot)

    def _open_block(self, slot: _Slot) -> None:
        """The block at ``slot.length``: the prompt's tail where it
        reaches in is known, the rest masked; a row past the request's
        budget stays masked and is never committed (nor served)."""
        b, base = self._diffusion, slot.length
        positions = base + np.arange(b)
        known = positions < slot.prompt.size
        tokens = np.zeros((b,), np.int32)
        tokens[known] = slot.prompt[base: base + int(known.sum())]
        slot.block = _BlockState(
            tokens=tokens, masked=~known,
            open=~known & (positions < slot.prompt.size + slot.max_new))

    def _accept_diffusion(self, decode_slots: List[_Slot],
                          picked: np.ndarray, commit: np.ndarray,
                          chunk: int = 0, touched: int = 0) -> None:
        """Host acceptance of one pass over the lanes' blocks.  A lane
        whose block had open rows ran a denoising pass: the rows the
        device chose are committed — and served: a token counts once,
        here — and the request retires with the token that fills its
        budget.  A lane whose block had none ran the commit pass: the
        finished block's K/V stand in the pool, its cached length
        advances by the block and the next block opens.  One
        ``kubeshare.engine.diffusion`` span a dispatch says what it
        carried: the lanes, their passes by kind, the query ``rows``
        they computed and how many of them went in masked, the tokens
        ``committed`` (served), the blocks done, the cached ``kv_rows``
        the lanes attended — and, of the same dispatch, the ``chunk``
        rows it carried beside them and the experts its routing
        ``touched``, so that a reader need not pair two spans."""
        b = self._diffusion
        now = time.monotonic()
        seen = dict(lanes=len(decode_slots), passes=0, commit_passes=0,
                    rows=b * len(decode_slots), masked_rows=0, committed=0,
                    blocks_done=0, kv_rows=0, chunk=chunk, touched=touched)
        for slot in decode_slots:
            block = slot.block
            seen["kv_rows"] += slot.length
            if not block.open.any():
                seen["commit_passes"] += 1
                seen["blocks_done"] += 1
                slot.generated.extend(self._block_generated(slot))
                slot.length += b
                self._open_block(slot)
                continue
            seen["passes"] += 1
            seen["masked_rows"] += int(block.masked.sum())
            rows = np.flatnonzero(commit[slot.idx])
            block.tokens[rows] = picked[slot.idx, rows]
            block.masked[rows] = block.open[rows] = False
            block.step += 1
            if rows.size:
                seen["committed"] += int(rows.size)
                self._serve_tokens(slot, int(rows.size), now)
            if len(slot.generated) + block.served >= slot.max_new:
                self._retire_diffusion(slot)
        # as the routing span: its attributes are what a trace's reader
        # can reach, its length is this bookkeeping's
        with profiling.span("kubeshare.engine.diffusion", **seen):
            self.diffusion_passes["denoise"] += seen["passes"]
            self.diffusion_passes["commit"] += seen["commit_passes"]
            self.diffusion_rows += seen["rows"]
            self.diffusion_tokens_committed += seen["committed"]
            self.diffusion_blocks += seen["blocks_done"]

    def _serve_tokens(self, slot: _Slot, count: int, now: float) -> None:
        """``count`` tokens of a diffusion lane are served: the counters,
        the tenant's charge, the first-token stamp and the token gaps."""
        slot.block.served += count
        self.tokens_generated += count
        self.tenant_tokens[slot.tenant] = \
            self.tenant_tokens.get(slot.tenant, 0) + count
        self._queue.charge(slot.tenant, count)
        gaps = count
        if slot.result.first_token_at is None:
            # a RESUMED slot keeps its original first-token time
            slot.result.first_token_at = now
            self._observe_ttft(slot.result.ttft, slot.tenant)
            gaps -= 1
        if gaps and slot.last_token_at is not None:
            self._observe_tbt((now - slot.last_token_at) / gaps, gaps,
                              slot.tenant)
        slot.last_token_at = now

    def _retire_diffusion(self, slot: _Slot) -> None:
        """The lane served the token that fills its budget: the request
        is done, with exactly ``max_new`` tokens in position order (the
        finished blocks', then the last block's up to the budget — no
        commit pass is run for a block nothing will read).  Only the
        prompt's whole diffusion blocks are indexed: the K/V of its tail
        were written beside generated rows of the same block."""
        b = self._diffusion
        self._retire(slot, slot.generated + self._block_generated(slot),
                     slot.prompt.size // b * b)

    def _block_generated(self, slot: _Slot) -> List[int]:
        """The generated tokens of the lane's block, in position order:
        its rows from the prompt's end to the request's budget."""
        first = max(slot.prompt.size - slot.length, 0)
        last = slot.prompt.size + slot.max_new - slot.length
        return [int(t) for t in slot.block.tokens[first:last]]

    def _retire_handoff(self, slot: _Slot) -> None:
        """Free a slot whose request just migrated out: index the
        prompt blocks (exactly :meth:`_maybe_retire`'s trie insert —
        the NEXT prompt sharing this prefix hits in THIS pool, where
        prefill happens), reclaim the chain, clear the slot.  The
        request is NOT finished: no finished_at, no requests_finished
        — the decode pool emits the rest and the router merges the
        counters without double-counting."""
        if self.prefix_index is not None:
            n_prompt = self.allocator.blocks_for_tokens(slot.prompt.size)
            prompt_blocks = [int(b) for b in slot.table[:n_prompt]]
            newly_cached, displaced = self.prefix_index.insert(
                slot.prompt, prompt_blocks)
            self.allocator.mark_cached(newly_cached)
            for b in displaced:
                self.allocator.uncache(b)
        self.allocator.reclaim(slot.blocks[::-1])
        slot._clear()
        slot.state = "free"

    def _accept_decode(self, decode_slots: List[_Slot],
                       emitted: np.ndarray, budgets: np.ndarray,
                       n_steps: Optional[int] = None) -> None:
        """Host acceptance for a decode span — or, with ``n_steps`` =
        units*span, for a device-loop ring drain.  The ring case is the
        span case verbatim: because the loop exits at the first span
        boundary where any lane deactivated, every accepted row was
        produced by an alive lane, and the budget cap / EOS truncation
        walk below reads exactly the rows K=1 consumes would have."""
        ec = self.engine_config
        span = ec.decode_span if n_steps is None else n_steps
        now = time.monotonic()
        for slot in decode_slots:
            i = slot.idx
            # mirror the device's lane-deactivation rule exactly: accept
            # min(budget, span) tokens, truncated at EOS (inclusive) —
            # every accepted token's K/V write happened on an alive lane
            take = min(int(budgets[i]), span)
            accepted = 0
            for t in range(take):
                tok = int(emitted[t, i])
                slot.length += 1
                slot.generated.append(tok)
                self.tokens_generated += 1
                accepted += 1
                if ec.eos_token is not None and tok == ec.eos_token:
                    break
            if accepted:
                if slot.drafter is not None:
                    slot.drafter.extend(slot.generated[-accepted:])
                self.tenant_tokens[slot.tenant] = \
                    self.tenant_tokens.get(slot.tenant, 0) + accepted
                self._queue.charge(slot.tenant, accepted)
                gap = now - (slot.last_token_at
                             if slot.last_token_at is not None else now)
                self._observe_tbt(gap / accepted, accepted, slot.tenant)
                slot.last_token_at = now
            self._maybe_retire(slot, slot.generated[-1])

    def _accept_verify(self, decode_slots: List[_Slot],
                       picked: np.ndarray, accepts: np.ndarray,
                       k_lanes: np.ndarray, budgets: np.ndarray) -> None:
        """Host-side acceptance for one verify chunk: each lane emits
        its accepted draft prefix plus the correction pick (the stream
        a sequential decode would have produced, position by position),
        truncated at its remaining budget and at EOS.  Also the one
        place the adaptive draft width learns: an EMA of per-round
        acceptance rate doubles the lane's width at >=0.75 and halves
        it at <=0.25 — powers of two only, so every width the
        controller can reach is a warmed bucket."""
        ec = self.engine_config
        now = time.monotonic()
        for slot in decode_slots:
            i = slot.idx
            k = int(k_lanes[i])
            # accepted proposal prefix, capped by the lane's own width
            # (pads carry -1 and can never match, but be explicit)
            m = min(int(accepts[i]), k)
            # emissions: m accepted drafts + the correction/bonus pick,
            # never past the request's remaining budget
            emit = min(m + 1, int(budgets[i]))
            accepted = 0
            for t in range(emit):
                tok = int(picked[i, t])
                slot.length += 1
                slot.generated.append(tok)
                self.tokens_generated += 1
                accepted += 1
                if ec.eos_token is not None and tok == ec.eos_token:
                    break
            if accepted:
                slot.drafter.extend(slot.generated[-accepted:])
                self.tenant_tokens[slot.tenant] = \
                    self.tenant_tokens.get(slot.tenant, 0) + accepted
                self._queue.charge(slot.tenant, accepted)
                gap = now - (slot.last_token_at
                             if slot.last_token_at is not None else now)
                self._observe_tbt(gap / accepted, accepted, slot.tenant)
                slot.last_token_at = now
            if k:
                rate = m / k
                slot.accept_rate = 0.5 * slot.accept_rate + 0.5 * rate
                if self._tuner is not None:
                    # autotune replaces the fixed doubling rule: the
                    # cost model's expected-tokens-per-dispatch argmax
                    # over warmed widths up to the tuned cap (the EMA
                    # stays maintained above as the rule's input)
                    slot.draft_width = self._tuner.lane_draft_width(
                        slot.accept_rate, self._draft_width_cap)
                elif slot.accept_rate >= 0.75:
                    slot.draft_width = min(slot.draft_width * 2,
                                           ec.draft_len)
                elif slot.accept_rate <= 0.25:
                    slot.draft_width = max(slot.draft_width // 2, 1)
                tenant = slot.tenant
                self.spec_drafted[tenant] = \
                    self.spec_drafted.get(tenant, 0) + k
                # EOS may cut emission short of the accepted prefix;
                # count only drafts that actually reached the stream
                self.spec_accepted[tenant] = \
                    self.spec_accepted.get(tenant, 0) + min(m, accepted)
                hist = self._spec_accept.setdefault(
                    tenant, [[0] * (len(SPEC_ACCEPT_BUCKETS) + 1), 0.0])
                hist[1] += rate
                _bucket_observe(hist[0], rate, SPEC_ACCEPT_BUCKETS)
            self._maybe_retire(slot, slot.generated[-1])

    def _observe_loop_exit(self, slots: List[_Slot],
                           emitted: np.ndarray, budgets: np.ndarray,
                           units: int, n_steps: int) -> None:
        """Derive the plain (v1) loop's exit reason from the drained
        ring BEFORE acceptance retires slots, and observe the realized
        fusion depth.  Priority: an EOS death beats a budget death
        beats running all K units (the v1 loop has no ring and no
        in-loop drafting, so ring_empty/redraft never apply)."""
        ec = self.engine_config
        eos_death = budget_death = False
        for slot in slots:
            i = slot.idx
            take = min(int(budgets[i]), n_steps)
            if ec.eos_token is not None and any(
                    int(emitted[t, i]) == ec.eos_token
                    for t in range(take)):
                eos_death = True
            elif int(budgets[i]) <= n_steps:
                budget_death = True
        if eos_death:
            reason = "stop"
        elif budget_death:
            reason = "retire"
        else:
            reason = "budget"
        self.loop_exit_reasons[reason] += 1
        self.loop_depth_sum += units
        self.loop_depth_count += 1

    def _accept_spec_loop(self, decode_slots: List[_Slot],
                          staged: List[_Slot], out_p: np.ndarray,
                          out_a: np.ndarray, out_d: np.ndarray,
                          units: int, head: int) -> None:
        """Host replay of a speculative loop launch: the device's
        per-unit acceptance walk, verbatim — unit u's lane i emitted
        ``min(accepted prefix + 1, remaining budget)`` tokens from
        ``out_p[u, i]``, truncated at EOS (inclusive), so the replay
        reconstructs exactly the stream K separate verify rounds would
        have produced.  Ring activations rebind a retired lane to the
        next staged entry in the device's exact order (lane index
        ascending within a span boundary, ring entries head-first);
        activated entries that survive the launch are bound into their
        lane's now-free engine slot, so later steps see them as
        ordinary decode lanes.

        Also the deferred unit-proportional bookkeeping half of
        :meth:`_run_spec_loop_step` (counters, collective charges,
        per-round adaptive-width updates), mirroring
        :meth:`_accept_verify` round for round."""
        ec = self.engine_config
        w = 1 + ec.draft_len
        self.verify_steps += units
        self.spec_loop_units += units
        self._count_weight_passes("spec_loop", units)
        for _ in range(units):
            self._charge_collectives(
                "verify_span", "verify", lanes=ec.num_slots, width=w)
        self.loop_depth_sum += units
        self.loop_depth_count += 1
        owner: Dict[int, _Slot] = {s.idx: s for s in decode_slots}
        dead: Dict[int, bool] = {s.idx: False for s in decode_slots}
        next_staged = 0
        unrefilled_eos = unrefilled_budget = False
        for u in range(units):
            now = time.monotonic()
            died: List[int] = []
            for i in sorted(owner):
                if dead[i]:
                    continue
                own = owner[i]
                k = int(out_d[u, i])
                m = int(out_a[u, i])
                rem = own.max_new - len(own.generated)
                emit = min(m + 1, rem)
                accepted = 0
                hit_eos = False
                for t in range(emit):
                    tok = int(out_p[u, i, t])
                    own.length += 1
                    own.generated.append(tok)
                    self.tokens_generated += 1
                    accepted += 1
                    if (ec.eos_token is not None
                            and tok == ec.eos_token):
                        hit_eos = True
                        break
                if accepted:
                    own.drafter.extend(own.generated[-accepted:])
                    self.tenant_tokens[own.tenant] = \
                        self.tenant_tokens.get(own.tenant, 0) \
                        + accepted
                    self._queue.charge(own.tenant, accepted)
                    gap = now - (own.last_token_at
                                 if own.last_token_at is not None
                                 else now)
                    self._observe_tbt(gap / accepted, accepted,
                                      own.tenant)
                    own.last_token_at = now
                if k:
                    rate = m / k
                    own.accept_rate = (0.5 * own.accept_rate
                                       + 0.5 * rate)
                    if self._tuner is not None:
                        own.draft_width = \
                            self._tuner.lane_draft_width(
                                own.accept_rate,
                                self._draft_width_cap)
                    elif own.accept_rate >= 0.75:
                        own.draft_width = min(own.draft_width * 2,
                                              ec.draft_len)
                    elif own.accept_rate <= 0.25:
                        own.draft_width = max(own.draft_width // 2, 1)
                    tenant = own.tenant
                    self.spec_drafted[tenant] = \
                        self.spec_drafted.get(tenant, 0) + k
                    self.spec_accepted[tenant] = \
                        self.spec_accepted.get(tenant, 0) \
                        + min(m, accepted)
                    hist = self._spec_accept.setdefault(
                        tenant,
                        [[0] * (len(SPEC_ACCEPT_BUCKETS) + 1), 0.0])
                    hist[1] += rate
                    _bucket_observe(hist[0], rate, SPEC_ACCEPT_BUCKETS)
                if hit_eos or len(own.generated) >= own.max_new:
                    self._maybe_retire(own, own.generated[-1])
                    died.append(i)
                    if next_staged >= head:
                        # this death went unrefilled: it can only be
                        # the exit unit (the cond checks occupied-but-
                        # dead lanes at every span boundary)
                        if hit_eos:
                            unrefilled_eos = True
                        else:
                            unrefilled_budget = True
            for i in died:
                if next_staged < head:
                    owner[i] = staged[next_staged]
                    next_staged += 1
                else:
                    dead[i] = True
        if next_staged != head:
            raise RuntimeError(
                f"spec-loop replay diverged: device activated {head} "
                f"ring entries, host replay saw {next_staged}")
        for i, own in owner.items():
            if own.idx == -1 and own.state == "decode":
                # an activated staged entry that survived the launch:
                # its lane's engine slot retired mid-loop, so the slot
                # is free — bind the survivor into it
                self._bind_staged(own, self._slots[i])
        for entry in staged[:next_staged]:
            self._ring_staged.remove(entry)
        if unrefilled_eos or unrefilled_budget:
            if ec.admission_ring > 0:
                reason = "ring_empty"
            elif unrefilled_eos:
                reason = "stop"
            else:
                reason = "retire"
        elif units < self._loop_k:
            reason = "redraft"
        else:
            reason = "budget"
        self.loop_exit_reasons[reason] += 1

    def _maybe_retire(self, slot: _Slot, token: int) -> None:
        eos = self.engine_config.eos_token
        if len(slot.generated) >= slot.max_new or (
                eos is not None and token == eos):
            self._retire(slot, list(slot.generated), slot.prompt.size)

    def _retire(self, slot: _Slot, tokens: List[int],
                indexed_rows: int) -> None:
        """The request is done: its result, the prompt's first
        ``indexed_rows`` rows into the prefix index, its blocks back."""
        result = slot.result
        # a preempted-and-resumed request's earlier incarnations'
        # tokens come first — the caller sees ONE contiguous stream
        result.tokens = slot.emitted_prefix + tokens
        result.finished_at = time.monotonic()
        if self.prefix_index is not None and indexed_rows:
            # index the prompt's blocks BEFORE dropping our refs:
            # insertion routes them to the idle-cached pool instead
            # of the free list (blocks past the prompt — pure decode
            # rows — free normally).  Blocks the trie already held
            # under identical tokens are simply not re-referenced;
            # a displaced block (our longer tail upgrading an
            # existing partial leaf) is uncached so its last reader
            # frees it.
            n_prompt = self.allocator.blocks_for_tokens(indexed_rows)
            prompt_blocks = [int(b) for b in slot.table[:n_prompt]]
            newly_cached, displaced = self.prefix_index.insert(
                slot.prompt[:indexed_rows], prompt_blocks)
            self.allocator.mark_cached(newly_cached)
            for b in displaced:
                self.allocator.uncache(b)
        # tail-first reclaim: see _preempt — eviction shaves chains
        # from the deepest block, preserving the shared head
        self.allocator.reclaim(slot.blocks[::-1])
        self._return_window(slot)
        self.requests_finished += 1
        slot._clear()
        slot.state = "free"
