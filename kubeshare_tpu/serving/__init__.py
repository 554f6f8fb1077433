"""Continuous-batching serving engine over a block-paged KV cache.

The serving subsystem the fractional-chip runtime was built to host:

- :mod:`kv_blocks` — a fixed-size-block KV pool with a free-list
  allocator (the cell allocator's reserve/reclaim discipline applied to
  HBM), so cache memory is charged per token actually generated instead
  of per ``max_seq_len`` slot;
- :mod:`paged` — the paged twins of the dense cached model steps
  (``models/decoding._decode_chunk``): chunked prefill writing straight
  into a slot's blocks, and a batched decode step where every slot sits
  at its OWN length;
- :mod:`engine` — the continuous-batching engine: one jitted step over a
  static pool of S slots with an active mask, admitting queued requests
  into freed slots mid-flight, FUSING a budget-bounded prefill chunk
  into the decode dispatch whenever both phases have work (stall-free
  mixed batching — decode lanes never wait behind a long prompt),
  retiring slots on EOS/max-tokens and recycling their blocks — zero
  recompilation after warmup, every dispatch chargeable through the
  :class:`~kubeshare_tpu.isolation.ExecutionGuard` token path, and the
  device sync guard-only so an unguarded engine pipelines one step
  ahead;
- :mod:`prefix_index` — the radix-tree prefix cache over the pool:
  retired prompts' blocks become content-addressable, admission maps
  matched blocks straight into a new slot's page table (refcounted
  sharing, copy-on-write on mid-block divergence) and prefill starts at
  the first uncached token; unreferenced cached blocks park in an LRU
  pool drained only when a reservation would otherwise fail;
- :mod:`drafter` — self-drafting speculative decoding's proposal side:
  a per-lane n-gram / prompt-lookup drafter (no second model) whose
  proposals the engine scores in ONE width-W verify dispatch
  (``paged.paged_verify_span``) and accepts by exact match against the
  target model's own picks — streams are bit-exact with speculation off
  by construction, greedy and sampled alike;
- :mod:`qos` — multi-tenant QoS inside the serving plane: a tenant
  registry (Guarantee/Opportunistic classes mirroring the scheduler's
  priority semantics, fair-share weights, per-tenant KV-HBM block
  quotas) and a token-weighted fair queue with tokend's decayed-share
  virtual-time accounting; admission pulls from it instead of FIFO, and
  a Guarantee admission the pool cannot fund preempts an Opportunistic
  decode slot — cache-backed, so the victim resumes bit-exactly from
  its first uncached token;
- :mod:`disagg` — disaggregated prefill/decode serving: a
  :class:`PrefillPool` and :class:`DecodePool` (role-restricted engine
  instances with independent allocators and warmup sets), a
  :class:`KVMigrator` moving finished prompts' block chains across on
  the versioned tier wire format (guard-only sync — unpacks overlap
  the decode pool's pipelined dispatch), and a :class:`DisaggRouter`
  front end preserving bit-exact streams across the handoff, with one
  shared host tier under both pools' prefix tries as the cross-pool
  cache bus;
- :mod:`sharded` — tensor-parallel serving: a
  :class:`ShardedServingContext` standing up a ``tp`` serving mesh,
  Megatron-style param sharding, a head-sharded paged KV pool, and
  ``shard_map`` twins of every paged dispatch (collectives INSIDE the
  one compiled program per plan kind, Ulysses re-shard for long
  prefill chunks) — streams bit-exact with the single-device engine
  by the no-partial-sums construction;
- :mod:`fleet` — replica fleet serving over the ``dp`` axis: a
  :class:`ReplicaFleet` front end standing up N engines (single-device,
  tp-sharded over carved device groups, or factory-built disagg pairs),
  routing each arrival by longest cached prefix
  (:class:`PrefixAffinityPolicy`, QoS-aware spill, pluggable), growing
  and shrinking online from the TTFT histogram families
  (:class:`TTFTBreachPolicy` with hysteresis), and draining retirees
  through the shared host tier so survivors inherit their caches —
  streams bit-exact with one monolithic engine at equal aggregate KV
  budget;
- :mod:`fabric` — the cluster KV fabric: a versioned, crc-framed
  message envelope over pluggable transports (in-process loopback,
  length-prefixed sockets), at-least-once :class:`FabricEndpoint`
  delivery (ack/dedup/TTL/bounded-backoff redelivery), a
  :class:`FabricDirectory` mapping prefix keys to owning replicas so a
  trie miss resolves to a remote promotion instead of a re-prefill,
  and an exportable prefix store serving cold prefixes across a
  process boundary — migration tickets, crash salvage, drain
  inheritance, and tier chains all ride this one bus;
- :mod:`metrics_view` — shared PromQL-style readers over the metrics
  plane: per-consumer interval windows over cumulative counters and
  histogram buckets (``increase()``), quantile estimation
  (``histogram_quantile()``), and snapshot flattening — the one
  implementation the autoscaler, the autotuner and a scraper all
  diff through;
- :mod:`autotune` — the cost-model-driven online autotuner: a
  per-dispatch-kind cost model fitted from the engine's own interval
  counters, a pluggable sandboxed :class:`TuningPolicy` interface
  (:class:`AnalyticPolicy` default, :class:`FittedTracePolicy` from a
  recorded trace), and an :class:`AutoTuner` retuning the
  RECOMPILE-FREE knob subset — fused-prefill budget, effective loop
  depth, draft-width cap, disagg pacing/reserve, fleet TTFT threshold
  — strictly inside the warmed-shape/validated-range envelope, so a
  bad policy can cost throughput but never a recompile or an invalid
  config.
"""

from .autotune import (AnalyticPolicy, AutoTuner, CostModel,
                       FittedTracePolicy, Knob, KnobSpec, KnobView,
                       TuningPolicy)
from .chaos import FaultClock, FaultPlan, ReplicaKilled
from .disagg import (DecodePool, DisaggRouter, DisaggTopology, KVMigrator,
                     PrefillPool)
from .drafter import NGramDrafter
from .engine import (EngineConfig, Request, RequestResult, ServingEngine,
                     plan_prefill_chunks)
from .fabric import (FabricDirectory, FabricEndpoint, FabricTransport,
                     LoopbackTransport, PrefixStoreClient, SocketTransport,
                     export_prefix_store, fabric_metric_families,
                     load_prefix_store, pack_message, pack_ticket,
                     prefix_fabric_key, recv_frame, send_frame,
                     serve_prefix_store, unpack_message, unpack_ticket)
from .fleet import (PrefixAffinityPolicy, ReplicaFleet, ReplicaHandle,
                    RoundRobinPolicy, RoutingPolicy, ScalingPolicy,
                    TTFTBreachPolicy)
from .kv_blocks import (BlockAllocator, BlockExhausted, PagedKVPool,
                        QuotaExceeded, chain_token_runs, init_paged_pool)
from .metrics_view import (CounterWindow, HistogramWindow, flatten_metrics,
                           hist_quantile, interval_quantile,
                           metric_histogram, metric_value)
from .kv_tier import (KV_CHAIN_VERSION, KV_WIRE_VERSION, DiskTier, HostTier,
                      LRUTierPolicy, QoSTierPolicy, TierPolicy,
                      WireCorruption, adopt_into, pack_block,
                      pack_chain, unpack_block, unpack_chain,
                      wire_block_bytes)
from .paged import (paged_copy_block, paged_decode_loop, paged_decode_span,
                    paged_decode_step, paged_gather_kv, paged_mixed_step,
                    paged_mixed_verify_step, paged_prefill_step,
                    paged_upload_block, paged_verify_span)
from .prefix_index import PrefixIndex
from .qos import (DEFAULT_TENANT, QOS_GUARANTEE, QOS_OPPORTUNISTIC,
                  FairQueue, TenantRegistry, TenantSpec)
from .sharded import (ShardDecision, ShardedServingContext,
                      carve_replica_groups, plan_sharding,
                      serving_sharding_rules)

__all__ = [
    "AnalyticPolicy",
    "AutoTuner",
    "BlockAllocator",
    "BlockExhausted",
    "CostModel",
    "CounterWindow",
    "DEFAULT_TENANT",
    "DecodePool",
    "DisaggRouter",
    "DisaggTopology",
    "DiskTier",
    "EngineConfig",
    "FabricDirectory",
    "FabricEndpoint",
    "FabricTransport",
    "FairQueue",
    "FaultClock",
    "FaultPlan",
    "FittedTracePolicy",
    "HistogramWindow",
    "HostTier",
    "KVMigrator",
    "KV_CHAIN_VERSION",
    "KV_WIRE_VERSION",
    "Knob",
    "KnobSpec",
    "KnobView",
    "LRUTierPolicy",
    "LoopbackTransport",
    "NGramDrafter",
    "PagedKVPool",
    "PrefillPool",
    "PrefixStoreClient",
    "PrefixAffinityPolicy",
    "PrefixIndex",
    "QoSTierPolicy",
    "TierPolicy",
    "WireCorruption",
    "QOS_GUARANTEE",
    "QOS_OPPORTUNISTIC",
    "QuotaExceeded",
    "ReplicaFleet",
    "ReplicaHandle",
    "ReplicaKilled",
    "Request",
    "RequestResult",
    "RoundRobinPolicy",
    "RoutingPolicy",
    "ScalingPolicy",
    "ServingEngine",
    "ShardDecision",
    "ShardedServingContext",
    "SocketTransport",
    "TTFTBreachPolicy",
    "TenantRegistry",
    "TenantSpec",
    "TuningPolicy",
    "adopt_into",
    "carve_replica_groups",
    "chain_token_runs",
    "export_prefix_store",
    "fabric_metric_families",
    "flatten_metrics",
    "hist_quantile",
    "init_paged_pool",
    "interval_quantile",
    "load_prefix_store",
    "metric_histogram",
    "metric_value",
    "pack_block",
    "pack_chain",
    "pack_message",
    "pack_ticket",
    "paged_copy_block",
    "paged_decode_loop",
    "paged_decode_span",
    "paged_decode_step",
    "paged_gather_kv",
    "paged_mixed_step",
    "paged_mixed_verify_step",
    "paged_prefill_step",
    "paged_upload_block",
    "paged_verify_span",
    "plan_prefill_chunks",
    "plan_sharding",
    "prefix_fabric_key",
    "recv_frame",
    "send_frame",
    "serve_prefix_store",
    "serving_sharding_rules",
    "unpack_block",
    "unpack_chain",
    "unpack_message",
    "unpack_ticket",
    "wire_block_bytes",
]
