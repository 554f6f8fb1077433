#!/usr/bin/env python3
"""Continuous batching vs run-to-completion serving bench (CPU-friendly).

Methodology (the serving section of docs/perf.md records results):

- ONE Poisson arrival trace of mixed-length requests (prompt and output
  lengths drawn independently) is replayed against two servers built
  from the same model weights and the SAME KV-cache HBM budget — the
  resource a fractional-chip serving pod is actually bounded by:

  * **run-to-completion** (the pre-engine serving path): FIFO batches of
    ``rtc_batch`` requests; each batch pads every prompt to the
    workload's max prompt bucket, prefills once, and decodes EVERYONE to
    the workload's max output length in one fused scan — the fixed
    worst-case shapes static serving must compile for.  Its dense cache
    reserves ``rtc_batch x max_seq_len`` rows for the whole run; that
    product IS the KV budget.
  * **continuous** (serving/engine.py): the same KV bytes as a block
    pool ((num_blocks-1) x block_size == rtc_batch x max_seq_len rows).
    Because admission reserves only what a request can actually touch,
    the same budget funds MORE concurrent slots — paging converts saved
    HBM into batch parallelism — on top of mid-flight admission, chunked
    prefill interleave, and per-request retirement.

- Useful tokens = each request's own requested output length (the
  run-to-completion server generates padding tokens past a request's
  need; they are not credited).  Aggregate tokens/s = useful tokens /
  wall time from first arrival to last completion.  TTFT and per-token
  latency are per-request wall times against the shared trace clock.

- Both servers are warmed up (compiled) before the clock starts, and
  the zero-recompile property is ASSERTED from jit cache stats after
  the run — a shape leak that recompiled mid-serve would invalidate the
  comparison (and, on TPU, the serving pod).

- Ratio methodology follows docs/perf.md: both sides pay the same
  fixed dispatch/measurement overheads on this host, so the
  continuous/run-to-completion RATIO is the trustworthy number;
  absolute tokens/s drift with host load.

- ``--shared-prefix`` switches to the PREFIX-CACHE comparison: one
  trace where a fraction of requests share a long common prompt prefix
  (the shared-system-prompt / few-shot-template traffic shape), replayed
  against the SAME engine geometry with the radix prefix cache enabled
  vs disabled — identical pool, identical KV-HBM budget, so the ratio
  isolates exactly what admission-time prefix matching + CoW + LRU
  eviction buy.  Skipped prefill tokens are read back from the new
  serving metrics families (the collector-plane scrape surface), not
  from bench-side arithmetic.

- ``--mixed`` switches to the STALL-FREE MIXED BATCHING comparison: one
  long-prompt/decode-mix trace (a short-prompt long-decode background
  keeps lanes decoding while a fraction of requests bring multi-chunk
  prompts) replayed against the same engine geometry with mixed
  batching on vs off — identical pool, identical KV-HBM budget, so the
  ratio isolates exactly what fusing a bounded prefill chunk into the
  decode dispatch buys.  Headline numbers: time-between-tokens p50/p99
  (read back through the metrics plane's per-class TBT histogram, not
  bench-side arithmetic) and aggregate tokens/s — and a hard assert
  that every request's stream is bit-exact between the two schedulers.

Run:

- ``--multi-tenant`` switches to the QoS comparison: one merged trace
  (a Guarantee tenant's paced stream + an Opportunistic flood arriving
  at t~0) replayed three ways at the SAME KV-HBM budget — the Guarantee
  trace alone (its entitled service), QoS on (class-priority fair
  queue, flood block quota, cache-backed preemption), and QoS off (the
  single-tenant FIFO engine).  Headline numbers: the Guarantee tenant's
  tokens/s retention and TTFT p50 ratio vs isolated, aggregate
  qos-on/qos-off tokens/s, preemption counts — and a hard assert that
  every request's stream is bit-exact between qos-on and qos-off
  (preempted requests resume through the prefix cache).

Run:

    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --smoke
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py            # full
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --shared-prefix
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --shared-prefix --smoke
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --multi-tenant
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --multi-tenant --smoke
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --mixed
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --mixed --smoke
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --tiered
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --tiered --smoke
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --disagg
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --disagg --smoke
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --sharded
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --sharded --smoke
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --fleet
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --fleet --smoke
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --fabric
    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py --fabric --smoke
    make serve-smoke serve-prefix-smoke serve-qos-smoke serve-mixed-smoke \
         serve-tier-smoke serve-disagg-smoke serve-sharded-smoke \
         serve-fleet-smoke serve-fabric-smoke

- ``--disagg`` switches to the DISAGGREGATED PREFILL/DECODE
  comparison: the long-prefill/steady-decode adversarial trace
  replayed through a :class:`DisaggRouter` (separate prefill and
  decode pools, finished prompts' KV chains migrated across on the
  versioned wire format) vs the monolithic MIXED engine at equal
  TOTAL KV-HBM budget — the split pools' allocatable blocks sum to
  the monolithic pool's, asserted.  Headline: decode-pool TBT p99
  (read through the metrics plane's ``pool``-labeled histogram) vs
  the monolithic arm's, at parity aggregate tokens/s, ABA-bracketed,
  with every stream hard-asserted identical across arms.

- ``--tiered`` switches to the KV-TIERING comparison: a many-distinct-
  shared-prefixes trace whose prefix working set exceeds the device
  pool's idle-cache capacity, replayed with the host-RAM tier on vs off
  (ABA-bracketed) plus an HBM-sized-pool reference arm — the headline
  is how much of the big pool's skipped-token rate the host tier
  recovers on the small pool (hit-rate, not HBM, setting the ceiling),
  with every stream hard-asserted identical across all arms.

- ``--sharded`` switches to the TENSOR-PARALLEL comparison: the
  long-prompt/decode-mix trace replayed through a tp-way sharded
  engine (``EngineConfig.mesh_spec``; Megatron-split params, a
  head-sharded paged KV pool, long prefill chunks routed through the
  Ulysses re-shard) vs the single-device engine at equal PER-DEVICE
  KV-HBM budget — the head-sharded pool stores ``kv_heads/tp`` of
  every block per device, so the sharded arm funds ``tp x`` the
  allocatable blocks at the same per-device bytes (asserted).
  ABA-bracketed, every stream hard-asserted identical, zero
  recompiles after warmup in both arms.  On the forced host-CPU mesh
  (``--xla_force_host_platform_device_count=4``) the collectives are
  memcpys over one physical core set and per-device FLOPs do not
  shrink, so the tokens/s ratio is PROVENANCE, not a headline —
  dispatch counts, collective-bytes estimates, and the tp-x KV
  capacity are the portable numbers (docs/perf.md).

- ``--fleet`` switches to the REPLICA-FLEET ROUTING comparison: a
  shared-prefix-heavy open-loop trace (several distinct prefix
  families) replayed through a 2-replica :class:`ReplicaFleet` with
  prefix-affinity routing vs the round-robin control — same fleet,
  same AGGREGATE KV-HBM budget (per-replica allocatable blocks sum to
  the monolithic pool's, asserted), affinity ABA-bracketed by two
  round-robin runs.  A monolithic single-engine run at the full
  budget anchors correctness: every stream is hard-asserted identical
  across all arms (routing changes where prompts prefill, never what
  they emit).  Headline: aggregate prefix-skip rate affinity vs
  round-robin, with the routing-decision mix read back through the
  fleet's merged metrics plane and zero recompiles asserted
  fleet-wide.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from kubeshare_tpu.utils.compile_cache import configure_compile_cache

configure_compile_cache()

import jax.numpy as jnp
import numpy as np


def smoke_settings() -> dict:
    """Seconds-fast CPU path (CI, tests/test_serving.py).
    KV budget: rtc_batch 4 x max_seq 96 = 384 rows = 48 blocks x 8
    (finer blocks pack the budget tighter — less internal
    fragmentation per request than coarse blocks would leave).
    One layer and a 16-wide chunk: the smokes lock mechanics, not
    ratios, and jit compiles dominate their CI bill."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, max_seq_len=96,
        num_requests=24, rtc_batch=4,
        num_slots=6, block_size=8, num_blocks=49,
        max_request_len=96, prefill_chunk=16,
        prompt_lo=8, prompt_hi=64, new_lo=4, new_hi=32,
        mean_interarrival_s=0.0005, seed=0,
    )


def default_settings() -> dict:
    """The capture configuration: big enough that a decode step
    amortizes host dispatch (the docs/perf.md round-5 lesson), mixed
    enough that padding waste is realistic.
    KV budget: rtc_batch 8 x max_seq 320 = 2560 rows = 160 blocks x 16."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=4096, max_seq_len=320,
        num_requests=64, rtc_batch=8,
        num_slots=12, block_size=16, num_blocks=161,
        max_request_len=320, prefill_chunk=64,
        prompt_lo=8, prompt_hi=192, new_lo=4, new_hi=96,
        mean_interarrival_s=0.005, seed=0,
    )


def shared_smoke_settings() -> dict:
    """Seconds-fast shared-prefix path (CI, tests/test_serving.py):
    60% of requests open with the same 44-token prefix — deliberately
    NOT a block multiple (block_size 8), so every hit ends mid-block
    and the copy-on-write dispatch runs in CI too."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, max_seq_len=96,
        num_requests=20,
        num_slots=4, block_size=8, num_blocks=49,
        max_request_len=96, prefill_chunk=16,
        prompt_lo=8, prompt_hi=64, new_lo=4, new_hi=16,
        shared_fraction=0.6, prefix_len=44, tail_lo=4, tail_hi=16,
        mean_interarrival_s=0.01, seed=0,
    )


def shared_settings() -> dict:
    """The shared-prefix capture configuration: 60% of requests share a
    256-token prefix (the acceptance shape) over the full-bench model;
    arrivals paced so the cache can warm the way live traffic warms it
    (the first sharer must retire before later sharers can hit)."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=4096, max_seq_len=320,
        num_requests=48,
        num_slots=12, block_size=16, num_blocks=161,
        max_request_len=320, prefill_chunk=64,
        prompt_lo=8, prompt_hi=192, new_lo=4, new_hi=32,
        # 256 + 16 + 32 = 304 rows worst case, inside max_request_len
        shared_fraction=0.6, prefix_len=256, tail_lo=8, tail_hi=16,
        mean_interarrival_s=0.02, seed=0,
    )


def qos_smoke_settings() -> dict:
    """Seconds-fast multi-tenant path (CI, tests/test_serving.py): a
    Guarantee tenant's steady stream under an Opportunistic flood that
    arrives all at once and would soak every slot and block FIFO."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, max_seq_len=96,
        num_slots=4, block_size=8, num_blocks=49,  # 48 blocks = 384 rows
        max_request_len=96, prefill_chunk=16,
        g_requests=6, g_prompt_lo=8, g_prompt_hi=32,
        g_new_lo=8, g_new_hi=16, g_mean_interarrival_s=0.02,
        # long-decode flood: every slot a flood request grabs stays busy
        # for dozens of spans, so Guarantee arrivals MUST preempt
        o_requests=16, o_prompt_lo=8, o_prompt_hi=24,
        o_new_lo=24, o_new_hi=48, o_mean_interarrival_s=0.001,
        o_quota_blocks=40,  # enough to soak all slots, not the pool
        seed=0,
    )


def qos_settings() -> dict:
    """The multi-tenant capture configuration (acceptance shape): the
    full-bench model, 12 Guarantee requests paced over the run, 36
    Opportunistic requests flooding from t=0 at one shared KV-HBM
    budget."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=4096, max_seq_len=320,
        num_slots=12, block_size=16, num_blocks=161,  # 160 blocks
        max_request_len=320, prefill_chunk=64,
        g_requests=12, g_prompt_lo=16, g_prompt_hi=128,
        g_new_lo=16, g_new_hi=64, g_mean_interarrival_s=0.25,
        # long-decode flood (see qos_smoke_settings): slots stay soaked
        o_requests=36, o_prompt_lo=16, o_prompt_hi=64,
        o_new_lo=64, o_new_hi=96, o_mean_interarrival_s=0.002,
        o_quota_blocks=120,  # enough to soak all slots, not the pool
        seed=0,
    )


def mixed_smoke_settings() -> dict:
    """Seconds-fast long-prompt/decode-mix path (CI,
    tests/test_serving.py): a short-prompt long-decode background keeps
    every lane decoding while every ~4th request brings a multi-chunk
    prompt — the traffic shape whose chunk dispatches stall every lane
    under the either/or scheduler."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, max_seq_len=192,
        num_requests=20,
        num_slots=5, block_size=8, num_blocks=121,  # 120 blocks = 960 rows
        max_request_len=192, prefill_chunk=16,
        short_prompt_lo=8, short_prompt_hi=24,
        short_new_lo=24, short_new_hi=40,
        long_fraction=0.25, long_prompt_lo=96, long_prompt_hi=160,
        long_new_lo=4, long_new_hi=12,
        mean_interarrival_s=0.02, seed=0,
    )


def mixed_settings() -> dict:
    """The mixed-batching capture configuration (acceptance shape): the
    full-bench model; one in eight requests brings a 3-5-chunk ingest
    prompt into a saturated pool of long-decode streamers.  decode_span
    2 keeps the decode cadence fine-grained — exactly the regime where
    the either/or scheduler's chunk stalls dominate the streamers' TBT
    tail and per-dispatch overhead is worth fusing away."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=4096, max_seq_len=320,
        num_requests=96,
        num_slots=6, block_size=16, num_blocks=121,  # 120 blocks
        max_request_len=320, prefill_chunk=64, decode_span=2,
        short_prompt_lo=16, short_prompt_hi=48,
        short_new_lo=96, short_new_hi=128,
        long_fraction=0.125, long_prompt_lo=192, long_prompt_hi=288,
        long_new_lo=8, long_new_hi=16,
        mean_interarrival_s=0.01, seed=0,
    )


def disagg_smoke_settings() -> dict:
    """Seconds-fast disaggregation path (CI, tests/test_serving.py):
    the mixed-batching smoke trace shape (short-prompt long-decode
    streamers + every ~4th request a multi-chunk ingest prompt)
    replayed disagg-on vs monolithic-mixed at ONE total KV-HBM budget,
    split: 120 allocatable blocks monolithic = 48 prefill + 72 decode
    (the decode pool keeps the bulk — it holds prompt AND generated
    rows for every live stream; prefill only prompt covers)."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, max_seq_len=192,
        num_requests=18,
        num_slots=5, block_size=8, num_blocks=121,   # 120 allocatable
        prefill_num_slots=2, prefill_num_blocks=49,  # 48
        decode_num_slots=5, decode_num_blocks=73,    # 72
        max_request_len=192, prefill_chunk=16,
        short_prompt_lo=8, short_prompt_hi=24,
        short_new_lo=24, short_new_hi=40,
        long_fraction=0.25, long_prompt_lo=96, long_prompt_hi=160,
        long_new_lo=4, long_new_hi=12,
        mean_interarrival_s=0.02, seed=0,
    )


def disagg_settings() -> dict:
    """The disaggregation capture configuration (acceptance shape):
    the full-bench model on the mixed-batching adversarial trace — one
    in eight requests brings a 3-5-chunk ingest prompt into a pool of
    long-decode streamers, decode_span 2 for a fine decode cadence
    (same span both arms).
    The monolithic-mixed arm fuses bounded prefill chunks into its
    decode dispatches (PR 4's best case); the disagg arm removes the
    contention instead of bounding it, so its decode-pool dispatches
    never carry prefill rows at all.  KV budget: 120 allocatable
    blocks monolithic = 40 prefill + 80 decode."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=4096, max_seq_len=320,
        num_requests=96,
        num_slots=6, block_size=16, num_blocks=121,   # 120 allocatable
        prefill_num_slots=2, prefill_num_blocks=41,   # 40
        decode_num_slots=6, decode_num_blocks=81,     # 80
        max_request_len=320, prefill_chunk=64, decode_span=2,
        short_prompt_lo=16, short_prompt_hi=48,
        short_new_lo=96, short_new_hi=128,
        long_fraction=0.125, long_prompt_lo=192, long_prompt_hi=288,
        long_new_lo=8, long_new_hi=16,
        # paced UNDER capacity (~500 tok/s offered vs ~600 tok/s the
        # monolithic arm serves on the capture host): both arms keep up
        # with arrivals, so throughput parity holds and the TBT tail
        # reflects per-token service latency — the thing
        # disaggregation changes — not unbounded backlog wait
        mean_interarrival_s=0.2, seed=0,
    )


def spec_smoke_settings() -> dict:
    """Seconds-fast speculative path (CI, tests/test_serving.py): a
    phrase-pool trace (every prompt tiles a few shared phrases — the
    templated/repetitive traffic prompt-lookup drafting exists for) on
    the 1-layer smoke model.  decode_span 1 makes a decode dispatch
    exactly one target-model forward pass, so dispatches-per-token is
    forward-passes-per-token on both arms (a span of W fuses W
    SEQUENTIAL forwards into one dispatch — orthogonal amortization
    the speculation criterion must not be conflated with); draft_len 8
    gives the drafter headroom."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, max_seq_len=192,
        num_requests=16,
        num_slots=4, block_size=8, num_blocks=121,
        max_request_len=160, prefill_chunk=16, decode_span=1,
        draft_len=8,
        num_phrases=4, phrase_len=6, phrases_per_prompt=3,
        prompt_reps=2, echo_len=24, new_lo=24, new_hi=48,
        # closed loop: every request queued at t=0 so both arms run at
        # identical full occupancy — open-loop pacing would penalize
        # the faster arm with a drained queue (fewer lanes per
        # dispatch) and make the dispatch counts timing-dependent
        mean_interarrival_s=0.0, seed=0,
    )


def spec_settings() -> dict:
    """The speculative capture configuration (acceptance shape): the
    full-bench model on the phrase-pool trace.  The criterion is
    dispatch-denominated, not wall-clock: at decode_span 1 every
    decode dispatch is one target-model forward pass emitting one
    token per lane; a verify dispatch is ALSO one forward pass but
    emits 1 + accepted tokens per drafting lane — self-drafted verify
    chunks on repetitive traffic must pay >= 1.3x fewer dispatches
    per emitted token, with every stream bit-identical to the
    sequential arm's."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=4096, max_seq_len=320,
        num_requests=48,
        num_slots=6, block_size=16, num_blocks=121,
        max_request_len=288, prefill_chunk=64, decode_span=1,
        draft_len=8,
        num_phrases=6, phrase_len=8, phrases_per_prompt=3,
        prompt_reps=2, echo_len=32, new_lo=48, new_hi=96,
        mean_interarrival_s=0.0, seed=0,   # closed loop (see smoke)
    )


def tiered_smoke_settings() -> dict:
    """Seconds-fast KV-tiering path (CI, tests/test_serving.py): five
    distinct 40-token shared prefixes (25 blocks of working set at
    block_size 8) over a 32-block device pool that can keep only a few
    of them cached at once — prefixes churn out of HBM between reuses,
    which is exactly the traffic the host tier exists to absorb."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, max_seq_len=96,
        num_requests=18,
        num_slots=3, block_size=8, num_blocks=33,     # 32 usable
        hbm_num_blocks=61,                            # the HBM-sized arm
        host_tier_bytes=400_000,                      # ~45 wire blocks
        max_request_len=96, prefill_chunk=16,
        num_prefixes=5, prefix_len=40, tail_lo=4, tail_hi=12,
        new_lo=4, new_hi=12,
        mean_interarrival_s=0.01, seed=0,
    )


def tiered_settings() -> dict:
    """The KV-tiering capture configuration (acceptance shape): eight
    distinct 128-token prefixes = 64 blocks of shared working set at
    block_size 16, served from an 80-block device pool (~1/2 the
    working set once live requests take their share) vs a 160-block
    HBM-sized pool; the host tier budget covers the full working set,
    so with tiering on the hit rate should track the big pool's."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=4096, max_seq_len=320,
        num_requests=56,
        num_slots=4, block_size=16, num_blocks=81,    # 80 usable
        hbm_num_blocks=161,                           # 160 usable
        host_tier_bytes=2_500_000,                    # ~75 wire blocks
        max_request_len=224, prefill_chunk=64,
        num_prefixes=8, prefix_len=128, tail_lo=8, tail_hi=24,
        new_lo=16, new_hi=48,
        mean_interarrival_s=0.02, seed=0,
    )


def fabric_smoke_settings() -> dict:
    """Seconds-fast cluster-KV-fabric path (CI, tests/test_serving.py):
    three distinct 64-token documents primed on a PUBLISHER engine
    whose tiny pool + tiny host tier force the demotion cascade onto
    the mmap disk arena, exported to a prefix store and served by a
    jax-free child PROCESS; the cold fabric-on arm fetches the chains
    over TCP and adopts them before its first arrival, so even the
    first touch of every document is a (remote-origin) tier hit
    instead of a cold prefill."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, max_seq_len=128,
        num_requests=12,
        num_slots=3, block_size=8, num_blocks=33,     # 32 usable
        host_tier_bytes=600_000,                      # ~70 wire blocks
        publisher_num_blocks=13,                      # 12 usable: churn
        publisher_host_tier_bytes=18_000,             # ~4 blocks: spill
        disk_tier_bytes=1 << 20,
        max_request_len=128, prefill_chunk=16,
        num_docs=3, doc_len=64, tail_lo=4, tail_hi=10,
        new_lo=4, new_hi=10, publisher_new=4,
        mean_interarrival_s=0.01, seed=0,
    )


def fabric_settings() -> dict:
    """The fabric capture configuration (acceptance shape): four
    192-token documents (48 blocks of shared working set at block_size
    16) published through a 16-block pool + ~12-block host tier — the
    cascade parks most of the corpus on disk — then promoted across
    the process boundary into a cold engine at the tiered bench's
    model scale."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=4096, max_seq_len=320,
        num_requests=40,
        num_slots=4, block_size=16, num_blocks=81,    # 80 usable
        host_tier_bytes=4_000_000,                    # ~120 wire blocks
        publisher_num_blocks=17,                      # 16 usable: churn
        publisher_host_tier_bytes=400_000,            # ~12 blocks: spill
        disk_tier_bytes=1 << 23,
        max_request_len=288, prefill_chunk=64,
        num_docs=4, doc_len=192, tail_lo=8, tail_hi=24,
        new_lo=16, new_hi=48, publisher_new=8,
        mean_interarrival_s=0.02, seed=0,
    )


def sharded_smoke_settings() -> dict:
    """Seconds-fast tensor-parallel path (CI, tests/test_serving.py):
    the long-prompt/decode-mix trace shape on a 1-layer MHA model
    whose 4 KV heads split one-per-device across the tp=4 host-CPU
    mesh (the bench locks the HEAD-SHARDED pool — the replicated-KV
    fallback is test coverage, not a capacity story).
    ``long_context_threshold == prefill_chunk`` routes every full
    prefill chunk through the Ulysses re-shard, so both attention
    layouts (sequence-sharded chunk attention and head-local decode)
    are exercised on one trace."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=4, d_ff=256,
        vocab_size=512, max_seq_len=192,
        num_requests=14,
        num_slots=4, block_size=8, num_blocks=41,   # 40 allocatable
        tp=4, long_context_threshold=16,
        max_request_len=160, prefill_chunk=16,
        short_prompt_lo=8, short_prompt_hi=24,
        short_new_lo=16, short_new_hi=32,
        long_fraction=0.25, long_prompt_lo=64, long_prompt_hi=120,
        long_new_lo=4, long_new_hi=12,
        mean_interarrival_s=0.02, seed=0,
    )


def sharded_settings() -> dict:
    """The tensor-parallel capture configuration (acceptance shape):
    the full-bench GQA model (8 query / 4 KV heads — two query heads
    per device attend their OWN device's KV shard) on the
    long-prompt/decode-mix trace, tp=4.  One in eight requests brings
    a multi-chunk ingest prompt whose full 64-token chunks cross
    ``long_context_threshold`` and route through Ulysses.  KV budget:
    the single-device arm's 120 allocatable blocks become 480 in the
    sharded arm at the SAME per-device bytes — the capacity win
    head-sharding exists for."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=4, d_ff=1024,
        vocab_size=4096, max_seq_len=384,
        num_requests=64,
        num_slots=6, block_size=16, num_blocks=121,  # 120 allocatable
        tp=4, long_context_threshold=64,
        max_request_len=320, prefill_chunk=64, decode_span=2,
        short_prompt_lo=16, short_prompt_hi=48,
        short_new_lo=64, short_new_hi=96,
        long_fraction=0.125, long_prompt_lo=192, long_prompt_hi=288,
        long_new_lo=8, long_new_hi=16,
        mean_interarrival_s=0.05, seed=0,
    )


def loop_smoke_settings() -> dict:
    """Seconds-fast device-loop path (CI, tests/test_serving.py): a
    decode-heavy trace — short prompts, ~100-token decodes — so most
    launches run their full K span-units and the planner-invocation
    drop is visible through CI noise.  One layer: the smokes lock
    mechanics (bit-exact streams, zero recompiles, the drop itself),
    not wall-clock ratios."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, max_seq_len=192,
        num_requests=12,
        num_slots=4, block_size=8, num_blocks=97,  # 96 blocks = 768 rows
        max_request_len=192, prefill_chunk=16,
        prompt_lo=8, prompt_hi=24, new_lo=96, new_hi=144,
        steps_per_launch=4,
        mean_interarrival_s=0.0005, seed=0,
    )


def loop_settings() -> dict:
    """The device-loop capture configuration (acceptance shape): the
    full-bench model on a decode-dominated trace (chat-style short
    prompts, 192-256-token completions) with K=8 — the regime where
    per-iteration host work (plan + marshal + dispatch) is the bill
    the device-resident loop exists to cut.  KV budget: 160 blocks x
    16 = 2560 rows = 8 slots x 320."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=4096, max_seq_len=320,
        num_requests=48,
        num_slots=8, block_size=16, num_blocks=161,
        max_request_len=320, prefill_chunk=64,
        prompt_lo=16, prompt_hi=48, new_lo=192, new_hi=256,
        steps_per_launch=8,
        mean_interarrival_s=0.002, seed=0,
    )


def loop_spec_smoke_settings() -> dict:
    """Seconds-fast verify-in-loop path (CI, make serve-loop-v2-smoke):
    the echoed phrase-pool trace — speculative AND decode-heavy, the
    traffic whose per-verify-span planner bill the v2 loop folds into
    one launch — on the 1-layer smoke model.  decode_span 1 keeps the
    undrafted-loop unit one forward pass; the smokes lock mechanics
    (streams bit-exact across v2/v1/K=1, zero recompiles, the spec
    loop actually firing), not wall-clock ratios."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, max_seq_len=256,
        num_requests=12,
        num_slots=4, block_size=8, num_blocks=121,
        max_request_len=224, prefill_chunk=16, decode_span=1,
        draft_len=4, steps_per_launch=4, admission_ring=2,
        num_phrases=4, phrase_len=6, phrases_per_prompt=3,
        prompt_reps=2, echo_len=24, new_lo=48, new_hi=80,
        mean_interarrival_s=0.0, seed=0,   # closed loop (see spec)
    )


def loop_spec_settings() -> dict:
    """The verify-in-loop capture configuration (acceptance shape):
    the full-bench model on the echoed phrase-pool trace at K=8 with a
    3-deep admission ring — speculative decode-heavy traffic where the
    v1 loop pays one planner invocation per verify span (every drafted
    round exits the device) and v2 pays one per K-unit launch.  The
    criterion: host planner invocations per emitted token >= 2x lower
    than the v1 loop, realized fusion depth read off the metrics
    plane, every stream bit-exact across all arms."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=4096, max_seq_len=320,
        num_requests=32,
        num_slots=6, block_size=16, num_blocks=161,
        max_request_len=288, prefill_chunk=64, decode_span=1,
        draft_len=8, steps_per_launch=8, admission_ring=3,
        num_phrases=6, phrase_len=8, phrases_per_prompt=3,
        prompt_reps=2, echo_len=32, new_lo=96, new_hi=160,
        mean_interarrival_s=0.0, seed=0,   # closed loop (see spec)
    )


def autotune_smoke_settings() -> dict:
    """Seconds-fast autotuner path (CI, make serve-autotune-smoke): a
    three-phase shifting trace (decode-heavy -> prefill-heavy ->
    draftable) against one engine with every tunable subsystem armed
    (mixed batching, the device loop, speculation).  The smoke locks
    mechanics — streams bit-exact tuned vs hand-set, zero recompiles
    in every arm, decisions confined to the warmed envelope — not
    wall-clock ratios."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, max_seq_len=192,
        requests_per_phase=5,
        num_slots=4, block_size=8, num_blocks=97,
        max_request_len=192, prefill_chunk=16,
        # decode-heavy phase: chat-shaped short prompts, long decodes
        decode_prompt_lo=8, decode_prompt_hi=16,
        decode_new_lo=48, decode_new_hi=64,
        # prefill-heavy phase: multi-chunk prompts, few output tokens
        prefill_prompt_lo=64, prefill_prompt_hi=128,
        prefill_new_lo=4, prefill_new_hi=8,
        # draftable phase: phrase-pool repetitive prompts the n-gram
        # drafter can actually continue
        num_phrases=6, phrase_len=8, phrases_per_prompt=3,
        prompt_reps=2, draft_new_lo=24, draft_new_hi=32,
        steps_per_launch=4, draft_len=4,
        hand_mixed_budget=16, autotune_interval=8,
        phase_gap_s=0.02,
        mean_interarrival_s=0.0005, seed=0,
    )


def autotune_settings() -> dict:
    """The autotuner capture configuration (acceptance shape): the
    full-bench model on the three-phase shifting trace, hand-set knobs
    frozen at values reasonable for the MIDDLE of the mix (K=8 loop,
    64-token fused budget) — the regime where a per-phase retune has
    something to reclaim.  KV budget matches the loop suite: 160
    blocks x 16 = 2560 rows."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=4096, max_seq_len=320,
        requests_per_phase=12,
        num_slots=8, block_size=16, num_blocks=161,
        max_request_len=320, prefill_chunk=64,
        decode_prompt_lo=16, decode_prompt_hi=48,
        decode_new_lo=128, decode_new_hi=192,
        prefill_prompt_lo=128, prefill_prompt_hi=256,
        prefill_new_lo=4, prefill_new_hi=12,
        num_phrases=8, phrase_len=12, phrases_per_prompt=4,
        prompt_reps=3, draft_new_lo=48, draft_new_hi=64,
        steps_per_launch=8, draft_len=8,
        hand_mixed_budget=64, autotune_interval=16,
        phase_gap_s=0.2,
        mean_interarrival_s=0.002, seed=0,
    )


def fleet_smoke_settings() -> dict:
    """Seconds-fast replica-fleet path (CI, make serve-fleet-smoke):
    a 2-replica fleet whose pools sum to the monolithic 48-block
    budget (24 allocatable each), on a 4-family shared-prefix trace.
    The 44-token prefix is deliberately NOT a block multiple so the
    mid-block tail path runs here too; arrivals are paced so a
    family's first request retires before its siblings arrive — the
    regime where the router's choice decides the hit rate."""
    return dict(
        d_model=128, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=512, max_seq_len=96,
        num_requests=24,
        num_slots=6, block_size=8, num_blocks=49,
        replicas=2, replica_num_slots=3,
        max_request_len=96, prefill_chunk=16,
        prompt_lo=8, prompt_hi=64, new_lo=4, new_hi=16,
        shared_fraction=0.8, num_groups=4, prefix_len=44,
        tail_lo=4, tail_hi=16,
        mean_interarrival_s=0.01, seed=0,
    )


def fleet_settings() -> dict:
    """The replica-fleet capture configuration: the full-bench model,
    2 replicas splitting the monolithic 160-block budget (80
    allocatable each), 6 prefix families of 256 tokens — a working set
    no single replica could have kept warm under round-robin.
    Arrivals at 200 ms mean: routing happens at SUBMIT time, so unlike
    the single-engine shared-prefix suite (where queued requests still
    hit at admission) the trace must be paced against service time for
    the router's probe to see a warm trie at all."""
    return dict(
        d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=4096, max_seq_len=320,
        num_requests=48,
        num_slots=12, block_size=16, num_blocks=161,
        replicas=2, replica_num_slots=6,
        max_request_len=320, prefill_chunk=64,
        prompt_lo=8, prompt_hi=192, new_lo=4, new_hi=32,
        shared_fraction=0.8, num_groups=6, prefix_len=256,
        tail_lo=8, tail_hi=16,
        mean_interarrival_s=0.2, seed=0,
    )


def chaos_smoke_settings() -> dict:
    """Seconds-fast chaos path (CI, make serve-chaos-smoke): the fleet
    smoke trace over a 2-replica fleet whose per-replica pool (16
    allocatable blocks = 128 tokens) sits BELOW the 4-family shared-
    prefix working set, so eviction pressure demotes warm prefixes to
    the shared host tier before the kill — the state crash salvage
    exists to recover.  The victim dies halfway through its fault-free
    step count (measured, not guessed)."""
    s = fleet_smoke_settings()
    s.update(
        num_blocks=33,  # 2 x 16 allocatable: tier pressure on purpose
        shared_tier_bytes=1 << 22,
        chaos_seed=7, chaos_victim="r1",
    )
    return s


def chaos_settings() -> dict:
    """The chaos capture configuration: the full fleet bench model and
    trace with the per-replica pool halved (40 allocatable blocks vs
    the 6 x 256-token family working set) so the shared tier holds real
    salvage when the victim dies mid-trace."""
    s = fleet_settings()
    s.update(
        num_blocks=81,  # 2 x 40 allocatable: below the working set
        shared_tier_bytes=1 << 24,
        chaos_seed=7, chaos_victim="r1",
    )
    return s


def build_tiered_workload(s: dict):
    """Many-distinct-shared-prefixes trace: every request opens with
    one of ``num_prefixes`` common ``prefix_len``-token prefixes
    (chosen uniformly — reuses of one prefix are interleaved with the
    others, so the small device pool churns between them) followed by
    a private tail.  Returns (trace, total shared-prefix tokens)."""
    rng = np.random.default_rng(s["seed"])
    prefixes = [rng.integers(0, s["vocab_size"],
                             s["prefix_len"]).astype(np.int32)
                for _ in range(s["num_prefixes"])]
    trace = []
    t = 0.0
    for i in range(s["num_requests"]):
        t += float(rng.exponential(s["mean_interarrival_s"]))
        prefix = prefixes[int(rng.integers(s["num_prefixes"]))]
        tail = rng.integers(
            0, s["vocab_size"],
            int(rng.integers(s["tail_lo"], s["tail_hi"] + 1)))
        prompt = np.concatenate([prefix, tail]).astype(np.int32)
        max_new = int(rng.integers(s["new_lo"], s["new_hi"] + 1))
        trace.append((f"req{i}", prompt, max_new, t))
    return trace, s["num_requests"] * s["prefix_len"]


def build_fabric_workload(s: dict):
    """Long-document corpus: ``num_docs`` shared ``doc_len``-token
    documents (the retrieval-context / long-system-prompt traffic
    shape); every request opens with one of them followed by a private
    tail.  Returns (documents, trace, total shared-document tokens) —
    the documents are what the publisher primes and the fabric-on arm
    fetches across the process boundary."""
    rng = np.random.default_rng(s["seed"])
    docs = [rng.integers(0, s["vocab_size"],
                         s["doc_len"]).astype(np.int32)
            for _ in range(s["num_docs"])]
    trace = []
    t = 0.0
    for i in range(s["num_requests"]):
        t += float(rng.exponential(s["mean_interarrival_s"]))
        doc = docs[int(rng.integers(s["num_docs"]))]
        tail = rng.integers(
            0, s["vocab_size"],
            int(rng.integers(s["tail_lo"], s["tail_hi"] + 1)))
        prompt = np.concatenate([doc, tail]).astype(np.int32)
        max_new = int(rng.integers(s["new_lo"], s["new_hi"] + 1))
        trace.append((f"req{i}", prompt, max_new, t))
    return docs, trace, s["num_requests"] * s["doc_len"]


def build_mixed_workload(s: dict):
    """Long-prompt/decode-mix trace: ``long_fraction`` of requests
    carry a multi-chunk prompt (and few output tokens — ingest-heavy
    traffic); the rest are short-prompt long-decode streamers whose
    inter-token latency the mixed scheduler protects.  Returns
    (trace, long_rids)."""
    rng = np.random.default_rng(s["seed"])
    trace, longs = [], set()
    t = 0.0
    for i in range(s["num_requests"]):
        t += float(rng.exponential(s["mean_interarrival_s"]))
        rid = f"req{i}"
        if rng.random() < s["long_fraction"]:
            prompt_len = int(rng.integers(
                s["long_prompt_lo"], s["long_prompt_hi"] + 1))
            max_new = int(rng.integers(
                s["long_new_lo"], s["long_new_hi"] + 1))
            longs.add(rid)
        else:
            prompt_len = int(rng.integers(
                s["short_prompt_lo"], s["short_prompt_hi"] + 1))
            max_new = int(rng.integers(
                s["short_new_lo"], s["short_new_hi"] + 1))
        prompt = rng.integers(0, s["vocab_size"], prompt_len).astype(np.int32)
        trace.append((rid, prompt, max_new, t))
    return trace, longs


def build_spec_workload(s: dict):
    """Phrase-pool repetitive trace: each prompt draws
    ``phrases_per_prompt`` phrases from a shared pool of
    ``num_phrases`` and tiles the sequence ``prompt_reps`` times —
    templated traffic whose n-grams repeat both WITHIN a prompt (the
    drafter's own window hits) and ACROSS requests (the trie's
    continuation hint hits on prefix-cache reuse)."""
    rng = np.random.default_rng(s["seed"])
    phrases = [rng.integers(0, s["vocab_size"],
                            s["phrase_len"]).astype(np.int32)
               for _ in range(s["num_phrases"])]
    trace = []
    t = 0.0
    for i in range(s["num_requests"]):
        t += float(rng.exponential(s["mean_interarrival_s"]))
        picks = rng.integers(0, s["num_phrases"],
                             s["phrases_per_prompt"])
        unit = np.concatenate([phrases[int(p)] for p in picks])
        prompt = np.tile(unit, s["prompt_reps"]).astype(np.int32)
        max_new = int(rng.integers(s["new_lo"], s["new_hi"] + 1))
        trace.append((f"req{i}", prompt, max_new, t))
    return trace


def echo_spec_trace(params, config, s: dict, trace):
    """Make the phrase-pool trace output-overlaps-input — the traffic
    prompt-lookup speculation exists for (summarization, code edits,
    RAG: the model re-emits spans it was given).  A random-weight
    bench model never copies its prompt, so the overlap is built the
    only honest way available: each prompt is extended with
    ``echo_len`` tokens of the model's OWN greedy continuation, making
    the generation's n-grams literally present in the prompt.

    A random model's continuations vary in self-similarity (some
    streams settle into short loops, others wander), so the trace
    oversamples ``spec_oversample``x base prompts, scores each
    candidate by replaying the prompt-lookup drafter over the
    continuation the engine will actually emit, and keeps the most
    draftable ones — the bench's job is to measure the verify
    machinery ON repetitive traffic, not to average it against
    undraftable noise.  All of this happens outside every timed arm
    and identically across them; arrival times and output budgets
    keep the original trace's draws."""
    from kubeshare_tpu.models.decoding import greedy_decode
    from kubeshare_tpu.serving.drafter import NGramDrafter

    over = int(s.get("spec_oversample", 4))
    cand_s = dict(s, num_requests=len(trace) * over)
    candidates = build_spec_workload(cand_s)
    prompts = np.stack([prompt for _, prompt, _, _ in candidates])
    # One batched dense decode covers both the echo span and the
    # region the engine will generate (bit-exact with the paged
    # engine's own greedy stream by construction).
    cont = np.asarray(greedy_decode(
        params, config, jnp.asarray(prompts),
        s["echo_len"] + s["new_hi"]))

    def draftability(i: int) -> float:
        drafter = NGramDrafter(
            3, list(prompts[i]) + list(cont[i][:s["echo_len"]]))
        gen = [int(t) for t in cont[i][s["echo_len"]:]]
        hits = 0
        for tok in gen:
            prop = drafter.propose(1)
            hits += bool(prop and prop[0] == tok)
            drafter.extend([tok])
        return hits / max(1, len(gen))

    ranked = sorted(range(len(candidates)),
                    key=lambda i: draftability(i), reverse=True)
    keep = sorted(ranked[:len(trace)])        # preserve arrival order
    return [
        (rid,
         np.concatenate([prompts[j],
                         cont[j][:s["echo_len"]]]).astype(np.int32),
         max_new, t)
        for (rid, _, max_new, t), j in zip(trace, keep)]


def build_qos_workload(s: dict):
    """One merged trace of two tenants: ``prod`` (Guarantee, Poisson
    paced) and ``batch`` (Opportunistic, near-simultaneous flood).
    Returns (trace sorted by arrival, tenant_of)."""
    rng = np.random.default_rng(s["seed"])
    trace, tenant_of = [], {}
    t = 0.0
    for i in range(s["g_requests"]):
        t += float(rng.exponential(s["g_mean_interarrival_s"]))
        rid = f"g{i}"
        prompt = rng.integers(
            0, s["vocab_size"],
            int(rng.integers(s["g_prompt_lo"], s["g_prompt_hi"] + 1))
        ).astype(np.int32)
        trace.append((rid, prompt,
                      int(rng.integers(s["g_new_lo"], s["g_new_hi"] + 1)),
                      t))
        tenant_of[rid] = "prod"
    t = 0.0
    for i in range(s["o_requests"]):
        t += float(rng.exponential(s["o_mean_interarrival_s"]))
        rid = f"o{i}"
        prompt = rng.integers(
            0, s["vocab_size"],
            int(rng.integers(s["o_prompt_lo"], s["o_prompt_hi"] + 1))
        ).astype(np.int32)
        trace.append((rid, prompt,
                      int(rng.integers(s["o_new_lo"], s["o_new_hi"] + 1)),
                      t))
        tenant_of[rid] = "batch"
    trace.sort(key=lambda entry: entry[3])
    return trace, tenant_of


def build_workload(s: dict):
    """One shared trace: (rid, prompt, max_new, arrival_offset_s)."""
    rng = np.random.default_rng(s["seed"])
    trace = []
    t = 0.0
    for i in range(s["num_requests"]):
        t += float(rng.exponential(s["mean_interarrival_s"]))
        prompt_len = int(rng.integers(s["prompt_lo"], s["prompt_hi"] + 1))
        max_new = int(rng.integers(s["new_lo"], s["new_hi"] + 1))
        prompt = rng.integers(0, s["vocab_size"], prompt_len).astype(np.int32)
        trace.append((f"req{i}", prompt, max_new, t))
    return trace


def build_shared_workload(s: dict):
    """Shared-prefix trace: ``shared_fraction`` of requests open with
    one common ``prefix_len``-token prefix followed by a private tail
    (few-shot template traffic); the rest are the mixed-length
    background.  Returns (trace, sharer_rids)."""
    rng = np.random.default_rng(s["seed"])
    prefix = rng.integers(0, s["vocab_size"], s["prefix_len"]).astype(np.int32)
    trace, sharers = [], set()
    t = 0.0
    for i in range(s["num_requests"]):
        t += float(rng.exponential(s["mean_interarrival_s"]))
        rid = f"req{i}"
        max_new = int(rng.integers(s["new_lo"], s["new_hi"] + 1))
        if rng.random() < s["shared_fraction"]:
            tail = rng.integers(
                0, s["vocab_size"],
                int(rng.integers(s["tail_lo"], s["tail_hi"] + 1)))
            prompt = np.concatenate([prefix, tail]).astype(np.int32)
            sharers.add(rid)
        else:
            prompt = rng.integers(
                0, s["vocab_size"],
                int(rng.integers(s["prompt_lo"], s["prompt_hi"] + 1))
            ).astype(np.int32)
        trace.append((rid, prompt, max_new, t))
    return trace, sharers


def build_fleet_workload(s: dict):
    """Shared-prefix-HEAVY trace for the replica-fleet comparison:
    ``shared_fraction`` of requests belong to one of ``num_groups``
    prefix families (each family shares its own ``prefix_len``-token
    opener — distinct system prompts / few-shot templates), the rest
    are mixed-length background.  Arrivals are open-loop Poisson on
    the shared clock.  Returns (trace, group_of) with group_of[rid]
    naming the family (None for background) — the bench aggregates
    skip rates per family and overall."""
    rng = np.random.default_rng(s["seed"])
    prefixes = [
        rng.integers(0, s["vocab_size"], s["prefix_len"]).astype(np.int32)
        for _ in range(s["num_groups"])]
    trace, group_of = [], {}
    t = 0.0
    for i in range(s["num_requests"]):
        t += float(rng.exponential(s["mean_interarrival_s"]))
        rid = f"req{i}"
        max_new = int(rng.integers(s["new_lo"], s["new_hi"] + 1))
        if rng.random() < s["shared_fraction"]:
            g = int(rng.integers(0, s["num_groups"]))
            tail = rng.integers(
                0, s["vocab_size"],
                int(rng.integers(s["tail_lo"], s["tail_hi"] + 1)))
            prompt = np.concatenate([prefixes[g], tail]).astype(np.int32)
            group_of[rid] = g
        else:
            prompt = rng.integers(
                0, s["vocab_size"],
                int(rng.integers(s["prompt_lo"], s["prompt_hi"] + 1))
            ).astype(np.int32)
            group_of[rid] = None
        trace.append((rid, prompt, max_new, t))
    return trace, group_of


def build_autotune_workload(s: dict):
    """Three-phase SHIFTING trace for the autotuner comparison: a
    decode-heavy phase (short prompts, long streamed decodes — the
    loop-depth/draft-width regime), then a prefill-heavy phase
    (multi-chunk prompts, few output tokens — the fused-budget
    regime), then a draftable phase (phrase-pool repetitive prompts
    the n-gram drafter can continue — the speculation regime), each of
    ``requests_per_phase`` requests with a ``phase_gap_s`` lull
    between phases so one regime drains before the next arrives.
    Returns (trace, phase_of) with phase_of[rid] naming the phase —
    the bench aggregates per-phase latency tuned vs hand-set."""
    rng = np.random.default_rng(s["seed"])
    phrases = [
        rng.integers(0, s["vocab_size"], s["phrase_len"]).astype(np.int32)
        for _ in range(s["num_phrases"])]
    trace, phase_of = [], {}
    t, i = 0.0, 0
    for phase in ("decode_heavy", "prefill_heavy", "draftable"):
        for _ in range(s["requests_per_phase"]):
            t += float(rng.exponential(s["mean_interarrival_s"]))
            rid = f"req{i}"
            i += 1
            if phase == "decode_heavy":
                prompt = rng.integers(
                    0, s["vocab_size"],
                    int(rng.integers(s["decode_prompt_lo"],
                                     s["decode_prompt_hi"] + 1))
                ).astype(np.int32)
                max_new = int(rng.integers(
                    s["decode_new_lo"], s["decode_new_hi"] + 1))
            elif phase == "prefill_heavy":
                prompt = rng.integers(
                    0, s["vocab_size"],
                    int(rng.integers(s["prefill_prompt_lo"],
                                     s["prefill_prompt_hi"] + 1))
                ).astype(np.int32)
                max_new = int(rng.integers(
                    s["prefill_new_lo"], s["prefill_new_hi"] + 1))
            else:
                picks = [phrases[int(rng.integers(s["num_phrases"]))]
                         for _ in range(s["phrases_per_prompt"])]
                prompt = np.concatenate(
                    picks * s["prompt_reps"]).astype(np.int32)
                prompt = prompt[:s["max_request_len"]
                                - s["draft_new_hi"] - 1]
                max_new = int(rng.integers(
                    s["draft_new_lo"], s["draft_new_hi"] + 1))
            phase_of[rid] = phase
            trace.append((rid, prompt, max_new, t))
        t += s["phase_gap_s"]
    return trace, phase_of


def _bench_model(s: dict):
    """The bench model every suite shares: config + initialized params
    from one settings dict (one definition — a drifted copy would
    silently benchmark a different model)."""
    from kubeshare_tpu.models.transformer import (
        TransformerConfig, transformer_init)

    config = TransformerConfig(
        vocab_size=s["vocab_size"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
        n_layers=s["n_layers"], d_ff=s["d_ff"],
        max_seq_len=s["max_seq_len"], dtype=jnp.float32,
        positional="rope", attention="reference")
    return config, transformer_init(jax.random.PRNGKey(s["seed"]), config)


def _percentiles(values, ps=(50, 95)):
    if not values:
        return {f"p{p}": None for p in ps}
    return {f"p{p}": float(np.percentile(np.asarray(values), p)) for p in ps}


# PromQL-style snapshot readers: the one shared implementation in
# serving/metrics_view.py (the autoscaler and the autotuner diff
# through the same module) — the bench keeps its historical underscore
# names at ~50 call sites.
from kubeshare_tpu.serving.metrics_view import (  # noqa: E402
    hist_quantile as _hist_quantile,
    metric_histogram as _metric_histogram,
    metric_value as _metric_value)


def run_continuous(params, config, s: dict, trace,
                   prefix_cache: bool = True, registry=None,
                   tenant_of=None, mixed: bool = True,
                   host_tier_bytes=None, num_blocks=None,
                   speculative: bool = False, tp=None,
                   long_context_threshold=None,
                   steps_per_launch: int = 1,
                   mixed_prefill_budget=None,
                   autotune: bool = False,
                   admission_ring: int = 0,
                   spec_loop: bool = True,
                   disk_tier_bytes=None, disk_tier_path=None,
                   preload=None) -> dict:
    from kubeshare_tpu.serving import EngineConfig, Request, ServingEngine

    mesh_spec = None
    if tp:
        from kubeshare_tpu.parallel.mesh import MeshSpec
        mesh_spec = MeshSpec(dp=1, tp=tp, sp=1)
    engine = ServingEngine(params, config, EngineConfig(
        num_slots=s["num_slots"], block_size=s["block_size"],
        num_blocks=(num_blocks if num_blocks is not None
                    else s["num_blocks"]),
        max_request_len=s["max_request_len"],
        prefill_chunk=s["prefill_chunk"], prefix_cache=prefix_cache,
        mixed=mixed, decode_span=s.get("decode_span", 4),
        mixed_prefill_budget=mixed_prefill_budget,
        host_tier_bytes=host_tier_bytes,
        tier_policy=s.get("tier_policy", "lru"),
        speculative=speculative, draft_len=s.get("draft_len", 8),
        mesh_spec=mesh_spec,
        long_context_threshold=long_context_threshold,
        steps_per_launch=steps_per_launch,
        autotune=autotune,
        autotune_interval=s.get("autotune_interval", 32),
        admission_ring=admission_ring,
        disk_tier_bytes=disk_tier_bytes,
        disk_tier_path=disk_tier_path),
        tenants=registry)
    if not spec_loop:
        # v1-loop reference arm (the loop-v2 suite's bracket): disarm
        # the speculative loop programs before warmup, so drafted
        # rounds leave the device for a standalone verify span and
        # only undrafted rounds take the plain device loop — the
        # per-span planner bill verify-in-loop exists to cut
        engine._spec_loops = {}
    engine.warmup()
    compiles_before = engine.compile_counts()
    if preload is not None:
        # fabric arm: remote chains adopted into the host tier BEFORE
        # the clock starts (a replica pre-warming off the fleet's
        # prefix bus).  Runs after the compile snapshot on purpose —
        # adoption is host-side bookkeeping and may not compile
        preload(engine)

    start = time.monotonic()
    pending = list(trace)
    while pending or not engine.idle:
        now = time.monotonic() - start
        while pending and pending[0][3] <= now:
            rid, prompt, max_new, _ = pending.pop(0)
            engine.submit(Request(
                rid, prompt, max_new,
                tenant=(tenant_of[rid] if tenant_of else "default")))
        if not engine.step() and pending:
            time.sleep(min(0.001, pending[0][3] - now))
    elapsed = time.monotonic() - start

    recompiles = sum(engine.compile_counts().values()) - sum(
        compiles_before.values())
    useful = sum(min(len(engine.result(rid).tokens), max_new)
                 for rid, _, max_new, _ in trace)
    ttfts, per_token = [], []
    requests = {}
    for rid, _, max_new, arrival in trace:
        r = engine.result(rid)
        ttfts.append((r.first_token_at - start) - arrival)
        if len(r.tokens) > 1:
            per_token.append(
                (r.finished_at - r.first_token_at) / (len(r.tokens) - 1))
        # raw per-request record for the multi-tenant suite (per-tenant
        # aggregation + the bit-exact resume check); callers pop it
        # before dumping JSON
        requests[rid] = {
            "arrival_s": arrival,
            "ttft_s": (r.first_token_at - start) - arrival,
            "finished_s": (r.finished_at - start) - arrival,
            "tokens": list(r.tokens),
        }
    # prefix-cache stats read back through the metrics surface (the
    # same families Prometheus scrapes), not private engine state
    metric = {(sm.name, tuple(sorted(sm.labels.items()))): sm.value
              for f in engine.collect_metrics() for sm in f.samples}
    preemptions = {
        labels[0][1]: int(v)
        for (name, labels), v in metric.items()
        if name == "kubeshare_serving_preemptions_total"}
    # time-between-tokens: read back through the metrics plane's TBT
    # histogram (the same series Prometheus scrapes), quantiles
    # estimated PromQL-style — per-token timestamps exist only there
    tbt_buckets = _metric_histogram(metric, "kubeshare_serving_tbt_seconds")
    return {
        "tokens_per_s": useful / elapsed,
        "useful_tokens": useful,
        "elapsed_s": elapsed,
        "ttft_s": _percentiles(ttfts),
        "per_token_s": _percentiles(per_token),
        "tbt_s": {"p50": _hist_quantile(tbt_buckets, 0.50),
                  "p99": _hist_quantile(tbt_buckets, 0.99)},
        "decode_steps": engine.decode_steps,
        "prefill_chunks": engine.prefill_chunks,
        "verify_steps": engine.verify_steps,
        "mixed_steps": int(_metric_value(
            metric, "kubeshare_serving_dispatches_total", kind="mixed")),
        "mixed_verify_steps": int(_metric_value(
            metric, "kubeshare_serving_dispatches_total",
            kind="mixed_verify")),
        # device-resident loop stats via the scrape surface: launches,
        # span-units they covered, and the host-overhead numerators the
        # loop exists to cut (planner invocations + per-phase seconds)
        "loop_launches": int(_metric_value(
            metric, "kubeshare_serving_dispatches_total", kind="loop")),
        "loop_units": int(_metric_value(
            metric, "kubeshare_serving_loop_units_total")),
        # device residency v2: speculative (verify-in-loop) launches
        # and their draft-verify units, loop exits by reason, and the
        # realized-fusion-depth summary — all read off the scrape
        # surface, never private engine state
        "spec_loop_launches": int(_metric_value(
            metric, "kubeshare_serving_dispatches_total",
            kind="spec_loop")),
        "spec_loop_units": int(_metric_value(
            metric, "kubeshare_serving_spec_loop_units_total")),
        "loop_exit_reasons": {
            dict(labels)["reason"]: int(v)
            for (name, labels), v in metric.items()
            if name == "kubeshare_serving_loop_exit_reason_total"},
        "loop_realized_depth": {
            "sum": float(_metric_value(
                metric, "kubeshare_serving_loop_realized_depth_sum")),
            "count": int(_metric_value(
                metric,
                "kubeshare_serving_loop_realized_depth_count"))},
        "planner_invocations": int(_metric_value(
            metric, "kubeshare_serving_host_planner_invocations_total")),
        "planner_per_token": _metric_value(
            metric, "kubeshare_serving_host_planner_invocations_total")
        / max(1, useful),
        "host_seconds": {
            dict(labels)["phase"]: float(v)
            for (name, labels), v in metric.items()
            if name == "kubeshare_serving_host_seconds_total"},
        # target-model dispatches per emitted token (decode spans +
        # verify chunks; prefill is phase-independent) — speculation's
        # headline denominator
        "dispatches_per_token":
            (engine.decode_steps + engine.verify_steps) / max(1, useful),
        # speculation stats via the scrape surface, per tenant
        "spec_drafted": {
            dict(labels)["tenant"]: int(v)
            for (name, labels), v in metric.items()
            if name == "kubeshare_serving_spec_tokens_total"
            and dict(labels)["kind"] == "drafted"},
        "spec_accepted": {
            dict(labels)["tenant"]: int(v)
            for (name, labels), v in metric.items()
            if name == "kubeshare_serving_spec_tokens_total"
            and dict(labels)["kind"] == "accepted"},
        "spec_acceptance_rounds": int(sum(
            v for (name, labels), v in metric.items()
            if name == "kubeshare_serving_spec_acceptance_ratio_count")),
        "spec_acceptance_mean": (
            float(sum(v for (name, labels), v in metric.items()
                      if name ==
                      "kubeshare_serving_spec_acceptance_ratio_sum"))
            / max(1, sum(
                v for (name, labels), v in metric.items()
                if name ==
                "kubeshare_serving_spec_acceptance_ratio_count"))),
        "kv_hbm_bytes_peak": engine.peak_blocks_in_use
        * engine.pool.bytes_per_block(),
        "prefix_hit_tokens": int(metric[
            ("kubeshare_serving_prefix_hit_tokens_total", ())]),
        "prefix_hit_requests": int(metric[
            ("kubeshare_serving_prefix_cache_requests_total",
             (("result", "hit"),))]),
        "cow_copies": int(_metric_value(
            metric, "kubeshare_serving_dispatches_total",
            kind="cow_copy")),
        # sharded engines report their collective traffic estimate via
        # the scrape surface; all-zero on a single-device engine
        "collective_bytes": {
            dict(labels)["kind"]: int(v)
            for (name, labels), v in metric.items()
            if name == "kubeshare_serving_collective_bytes_total"},
        "warmup_compiles": {k: int(v) for k, v in compiles_before.items()},
        # the eviction family grew a `reason` label (tiering PR): sum
        # for the total, keep the per-reason split alongside
        "evicted_blocks": int(sum(
            v for (name, _), v in metric.items()
            if name == "kubeshare_serving_prefix_evicted_blocks_total")),
        "evictions_by_reason": {
            dict(labels)["reason"]: int(v)
            for (name, labels), v in metric.items()
            if name == "kubeshare_serving_prefix_evicted_blocks_total"},
        "tier": {
            "demoted": int(metric[("kubeshare_serving_tier_blocks_total",
                                   (("event", "demoted"),))]),
            "promoted": int(metric[("kubeshare_serving_tier_blocks_total",
                                    (("event", "promoted"),))]),
            "dropped": int(metric[("kubeshare_serving_tier_blocks_total",
                                   (("event", "dropped"),))]),
            "host_evicted": int(metric[
                ("kubeshare_serving_tier_blocks_total",
                 (("event", "host_evicted"),))]),
            "hit_requests": int(metric[
                ("kubeshare_serving_tier_requests_total",
                 (("result", "hit"),))]),
            "hit_tokens": int(metric[
                ("kubeshare_serving_tier_hit_tokens_total", ())]),
            "host_bytes_used": int(metric[
                ("kubeshare_serving_tier_host_bytes",
                 (("kind", "used"),))]),
            "promotion_stall_s": float(metric[
                ("kubeshare_serving_tier_promotion_stall_seconds_total",
                 ())]),
        },
        # fabric/disk observability (all-zero without the tiers): the
        # remote-vs-local tier-hit split and the disk arena counters,
        # read off the same scrape surface
        "tier_hit_origin": {
            "local": int(_metric_value(
                metric,
                "kubeshare_serving_tier_hit_origin_requests_total",
                origin="local")),
            "remote": int(_metric_value(
                metric,
                "kubeshare_serving_tier_hit_origin_requests_total",
                origin="remote")),
        },
        "disk": {
            "demoted": int(_metric_value(
                metric, "kubeshare_serving_disk_tier_blocks_total",
                event="demoted")),
            "promoted": int(_metric_value(
                metric, "kubeshare_serving_disk_tier_blocks_total",
                event="promoted")),
            "evicted": int(_metric_value(
                metric, "kubeshare_serving_disk_tier_blocks_total",
                event="evicted")),
            "refused": int(_metric_value(
                metric, "kubeshare_serving_disk_tier_blocks_total",
                event="refused")),
            "corrupt_read": int(_metric_value(
                metric, "kubeshare_serving_disk_tier_blocks_total",
                event="corrupt_read")),
            "bytes_used": int(_metric_value(
                metric, "kubeshare_serving_disk_tier_bytes",
                kind="used")),
        },
        "preemptions": preemptions,
        "recompiles": recompiles,
        "requests": requests,
        # autotuner observability (empty with autotune off): the knob
        # trajectory [(round, knob, old, new)] and the decision
        # counters, read from the tuner itself — the same numbers the
        # kubeshare_serving_tuner_decisions_total family exports
        "tuner": {
            "decisions": {f"{k}:{d}": int(n) for (k, d), n in sorted(
                engine._tuner.decisions.items())},
            "trajectory": [list(t) for t in engine._tuner.trajectory],
        } if engine._tuner is not None else None,
    }


def run_disagg(params, config, s: dict, trace, registry=None,
               tenant_of=None) -> dict:
    """Disaggregated arm: one :class:`DisaggRouter` (prefill pool +
    decode pool + KV migration) replayed with the same open-loop drive
    as ``run_continuous``.  Latency families are read back through the
    metrics plane's ``pool``-labeled histograms PromQL-style — the
    decode-pool TBT series is the headline (those are the lanes whose
    tail contention with long prompts disaggregation removes).

    With >= 2 devices the pools are placed on separate slices of a
    2-slice virtual mesh (``DisaggTopology("virtual_multislice")`` —
    the dp-over-DCN deployment shape) so their dispatches genuinely
    overlap; on one device they fall back to ``two_cell`` and
    serialize, which understates disaggregation on CPU.  Handoff
    backpressure is capped at the decode pool's slot count — prefill
    never runs further ahead than decode can absorb."""
    from kubeshare_tpu.constants import (ENV_MEGASCALE_NUM_SLICES,
                                         ENV_MEGASCALE_SLICE_ID)
    from kubeshare_tpu.parallel.distributed import multislice_spec_from_env
    from kubeshare_tpu.serving import (DisaggRouter, DisaggTopology,
                                       EngineConfig, Request)

    topology = None
    if len(jax.devices()) >= 2:
        topology = DisaggTopology("virtual_multislice", multislice_spec_from_env(
            {ENV_MEGASCALE_NUM_SLICES: "2", ENV_MEGASCALE_SLICE_ID: "0"}))
    shared = dict(
        block_size=s["block_size"], max_request_len=s["max_request_len"],
        prefill_chunk=s["prefill_chunk"],
        decode_span=s.get("decode_span", 4))
    router = DisaggRouter(
        params, config,
        EngineConfig(num_slots=s["prefill_num_slots"],
                     num_blocks=s["prefill_num_blocks"], **shared),
        EngineConfig(num_slots=s["decode_num_slots"],
                     num_blocks=s["decode_num_blocks"], **shared),
        tenants=registry, topology=topology,
        max_pending_handoffs=s.get("max_pending_handoffs",
                                   s["decode_num_slots"]),
        decode_priority=s.get("decode_priority"))
    router.warmup()
    compiles_before = router.compile_counts()

    start = time.monotonic()
    pending = list(trace)
    while pending or not router.idle:
        now = time.monotonic() - start
        while pending and pending[0][3] <= now:
            rid, prompt, max_new, _ = pending.pop(0)
            router.submit(Request(
                rid, prompt, max_new,
                tenant=(tenant_of[rid] if tenant_of else "default")))
        if not router.step() and pending:
            time.sleep(min(0.001, pending[0][3] - now))
    elapsed = time.monotonic() - start

    recompiles = sum(router.compile_counts().values()) - sum(
        compiles_before.values())
    useful = sum(min(len(router.result(rid).tokens), max_new)
                 for rid, _, max_new, _ in trace)
    ttfts, per_token = [], []
    requests = {}
    for rid, _, max_new, arrival in trace:
        r = router.result(rid)
        ttfts.append((r.first_token_at - start) - arrival)
        if len(r.tokens) > 1:
            per_token.append(
                (r.finished_at - r.first_token_at) / (len(r.tokens) - 1))
        requests[rid] = {
            "arrival_s": arrival,
            "ttft_s": (r.first_token_at - start) - arrival,
            "finished_s": (r.finished_at - start) - arrival,
            "tokens": list(r.tokens),
        }
    metric = {(sm.name, tuple(sorted(sm.labels.items()))): sm.value
              for f in router.collect_metrics() for sm in f.samples}

    def pool_hist(name, pool):
        view = {k: v for k, v in metric.items()
                if dict(k[1]).get("pool") == pool}
        return _metric_histogram(view, name)

    tbt_all = _metric_histogram(metric, "kubeshare_serving_tbt_seconds")
    tbt_by_pool = {
        pool: {"p50": _hist_quantile(b, 0.50),
               "p99": _hist_quantile(b, 0.99)}
        for pool in ("prefill", "decode")
        for b in [pool_hist("kubeshare_serving_tbt_seconds", pool)]}
    # TTFT-by-pool via histogram_quantile over the pool-labeled series:
    # prefill observes submit->first-token (the user-visible TTFT);
    # decode observes handoff->first-decode-token (the migration lag)
    ttft_by_pool = {
        pool: {"p50": _hist_quantile(b, 0.50),
               "p95": _hist_quantile(b, 0.95)}
        for pool in ("prefill", "decode")
        for b in [pool_hist("kubeshare_serving_ttft_seconds", pool)]}
    stall_buckets = _metric_histogram(
        metric, "kubeshare_serving_migration_stall_seconds")
    stall_count = int(metric[
        ("kubeshare_serving_migration_stall_seconds_count", ())])
    stall_sum = float(metric[
        ("kubeshare_serving_migration_stall_seconds_sum", ())])
    preemptions = {
        dict(labels)["tenant"]: int(v)
        for (name, labels), v in metric.items()
        if name == "kubeshare_serving_preemptions_total"}
    dispatches = {
        f"{dict(labels)['pool']}.{dict(labels)['kind']}": int(v)
        for (name, labels), v in metric.items()
        if name == "kubeshare_serving_dispatches_total"
        and dict(labels)["kind"] in ("prefill_chunk", "decode_span",
                                     "verify_span", "mixed")
        and v}
    return {
        "topology": (topology.mode if topology is not None
                     else "two_cell"),
        "tokens_per_s": useful / elapsed,
        "useful_tokens": useful,
        "elapsed_s": elapsed,
        "ttft_s": _percentiles(ttfts),
        "per_token_s": _percentiles(per_token),
        "tbt_s": {"p50": _hist_quantile(tbt_all, 0.50),
                  "p99": _hist_quantile(tbt_all, 0.99)},
        "tbt_by_pool_s": tbt_by_pool,
        "ttft_by_pool_s": ttft_by_pool,
        "dispatches": dispatches,
        "prefill_chunks": router.prefill.prefill_chunks,
        "decode_steps": router.decode.decode_steps,
        "verify_steps": router.decode.verify_steps,
        "migration": {
            "packed": int(metric[("kubeshare_serving_migrations_total",
                                  (("stage", "packed"),))]),
            "delivered": int(metric[("kubeshare_serving_migrations_total",
                                     (("stage", "delivered"),))]),
            "migrated_bytes": int(metric[
                ("kubeshare_serving_migrated_bytes_total", ())]),
            "stall_s": {"p50": _hist_quantile(stall_buckets, 0.50),
                        "p99": _hist_quantile(stall_buckets, 0.99),
                        "mean": stall_sum / max(1, stall_count),
                        "count": stall_count},
        },
        "kv_hbm_bytes_peak":
            router.prefill.peak_blocks_in_use
            * router.prefill.pool.bytes_per_block()
            + router.decode.peak_blocks_in_use
            * router.decode.pool.bytes_per_block(),
        "preemptions": preemptions,
        "recompiles": recompiles,
        "requests": requests,
    }


def run_fleet(params, config, s: dict, trace, routing=None,
              fault_clock=None, shared_tier_bytes=None,
              on_step=None) -> dict:
    """Replica-fleet arm: one :class:`ReplicaFleet` of ``replicas``
    engines, each funded with 1/N of the monolithic arm's allocatable
    KV blocks, replayed with the same open-loop drive as
    ``run_continuous``.  ``routing=None`` takes the fleet's default
    :class:`PrefixAffinityPolicy`; the round-robin control passes
    ``RoundRobinPolicy()``.  Skipped-prefix and routing stats are read
    back through the merged metrics plane (the collector scrape
    surface), not bench-side arithmetic.

    ``fault_clock`` wires a chaos :class:`FaultClock` through the fleet
    (and becomes its internal clock — recovery latency is then VIRTUAL
    time, deterministic run to run); ``shared_tier_bytes`` stands up
    the shared host tier crash salvage needs.  The fault-free chaos arm
    passes an empty-plan clock so both arms share identical wiring.
    ``on_step(fleet)`` runs once per drive iteration — the chaos bench
    uses it to arm the kill only once the victim is mid-stream."""
    from kubeshare_tpu.serving import EngineConfig, ReplicaFleet, Request

    replicas = s["replicas"]
    replica_blocks = (s["num_blocks"] - 1) // replicas + 1
    fleet = ReplicaFleet(
        params, config,
        EngineConfig(
            num_slots=s["replica_num_slots"], block_size=s["block_size"],
            num_blocks=replica_blocks,
            max_request_len=s["max_request_len"],
            prefill_chunk=s["prefill_chunk"],
            decode_span=s.get("decode_span", 4)),
        replicas=replicas, routing=routing, fault_clock=fault_clock,
        shared_tier_bytes=shared_tier_bytes)
    fleet.warmup()
    compiles_before = fleet.compile_counts()

    start = time.monotonic()
    pending = list(trace)
    while pending or not fleet.idle:
        now = time.monotonic() - start
        while pending and pending[0][3] <= now:
            rid, prompt, max_new, _ = pending.pop(0)
            fleet.submit(Request(rid, prompt, max_new))
        if on_step is not None:
            on_step(fleet)
        if not fleet.step() and pending:
            time.sleep(min(0.001, pending[0][3] - now))
    elapsed = time.monotonic() - start

    recompiles = sum(fleet.compile_counts().values()) - sum(
        compiles_before.values())
    useful = sum(min(len(fleet.result(rid).tokens), max_new)
                 for rid, _, max_new, _ in trace)
    prompt_tokens = sum(len(prompt) for _, prompt, _, _ in trace)
    ttfts = []
    requests = {}
    for rid, _, max_new, arrival in trace:
        r = fleet.result(rid)
        ttfts.append((r.first_token_at - start) - arrival)
        requests[rid] = {
            "arrival_s": arrival,
            "ttft_s": (r.first_token_at - start) - arrival,
            "owner": fleet.owner_of(rid),
            "tokens": list(r.tokens),
        }
    metric = {(sm.name, tuple(sorted(sm.labels.items()))): sm.value
              for f in fleet.collect_metrics() for sm in f.samples}
    hit_tokens = int(_metric_value(
        metric, "kubeshare_serving_prefix_hit_tokens_total"))
    per_replica_dispatches = {}
    for (name, labels), v in metric.items():
        if name != "kubeshare_serving_dispatches_total":
            continue
        rep = dict(labels).get("replica")
        if rep:
            per_replica_dispatches[rep] = (
                per_replica_dispatches.get(rep, 0) + int(v))
    return {
        "replicas": replicas,
        "kv_blocks_per_replica": replica_blocks - 1,
        "tokens_per_s": useful / elapsed,
        "useful_tokens": useful,
        "elapsed_s": elapsed,
        "ttft_s": _percentiles(ttfts),
        # the headline numerator: prompt tokens NOT prefilled because a
        # replica's radix trie already held them
        "prefix_hit_tokens": hit_tokens,
        "prefix_skip_rate": hit_tokens / max(1, prompt_tokens),
        "prefix_hit_requests": int(_metric_value(
            metric, "kubeshare_serving_prefix_cache_requests_total",
            result="hit")),
        "routing_decisions": {
            dict(labels)["reason"]: int(v)
            for (name, labels), v in metric.items()
            if name == "kubeshare_serving_fleet_routing_decisions_total"},
        "per_replica_dispatches": per_replica_dispatches,
        "recompiles": recompiles,
        "requests": requests,
        # health-monitor ledger (all zeros on a fault-free run)
        "replica_failures": dict(fleet.replica_failures),
        "salvaged_prefix_tokens": fleet.salvaged_tokens,
        "salvage_candidate_tokens": fleet.salvage_candidate_tokens,
        "orphans_readmitted": fleet.orphans_readmitted,
        "recovery_durations_s": list(fleet.recovery_durations),
    }


def run_fleet_bench(s: dict, aba: bool = True) -> dict:
    """Prefix-affinity routing vs round-robin over a 2-replica fleet at
    equal AGGREGATE KV budget (replicas x per-replica allocatable ==
    monolithic allocatable — asserted), on one shared-prefix-heavy
    open-loop trace.  The affinity run is ABA-bracketed by two
    round-robin runs (first-trace host costs bias whichever arm runs
    first); a monolithic single-engine run at the full budget anchors
    bit-exactness — every stream is hard-asserted identical across ALL
    arms, so routing provably never changes tokens, only where prompts
    prefill.  Headline: aggregate prefix-skip rate affinity vs
    round-robin (the router's whole contribution), with the routing
    decision mix alongside and zero recompiles asserted fleet-wide.
    ``aba=False`` drops the bracketing second round-robin run."""
    from kubeshare_tpu.serving import RoundRobinPolicy

    config, params = _bench_model(s)
    replicas = s["replicas"]
    mono_blocks = s["num_blocks"] - 1
    if mono_blocks % replicas:
        raise ValueError(
            f"monolithic budget of {mono_blocks} allocatable blocks "
            f"does not split across {replicas} replicas — the "
            f"equal-aggregate-HBM comparison needs an even carve")
    trace, group_of = build_fleet_workload(s)
    shared_requests = sum(1 for g in group_of.values() if g is not None)

    mono = run_continuous(params, config, s, trace, mixed=True)
    off_a = run_fleet(params, config, s, trace,
                      routing=RoundRobinPolicy())
    on = run_fleet(params, config, s, trace)  # default = affinity
    off_b = (run_fleet(params, config, s, trace,
                       routing=RoundRobinPolicy()) if aba else off_a)
    per_replica = on["kv_blocks_per_replica"]
    if per_replica * replicas != mono_blocks:
        raise ValueError(
            f"fleet budget {replicas}x{per_replica} allocatable blocks "
            f"!= monolithic {mono_blocks} — the equal-aggregate-HBM "
            f"claim is broken")
    recompiles = (on["recompiles"] + off_a["recompiles"]
                  + (off_b["recompiles"] if aba else 0)
                  + mono["recompiles"])
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — a static-shape "
            f"leak; the comparison (and a TPU serving pod) is invalid")
    mismatched = [
        rid for rid, _, _, _ in trace
        if not (mono["requests"][rid]["tokens"]
                == on["requests"][rid]["tokens"]
                == off_a["requests"][rid]["tokens"]
                == off_b["requests"][rid]["tokens"])]
    if mismatched:
        raise RuntimeError(
            f"streams diverged across fleet/monolithic arms for "
            f"{mismatched} — replica routing is NOT bit-exact")
    for arm in (mono, on, off_a) + ((off_b,) if aba else ()):
        arm.pop("requests")
    mono.pop("recompiles", None)
    off_skip = (off_a["prefix_skip_rate"] + off_b["prefix_skip_rate"]) / 2
    off_tps = (off_a["tokens_per_s"] + off_b["tokens_per_s"]) / 2
    return {
        "suite": "serving-fleet",
        "metric": "aggregate prefix-skip rate, affinity routing vs "
                  "round-robin over the same fleet (same shared-prefix "
                  "Poisson trace, same aggregate KV-HBM budget; skips "
                  "read through the merged metrics plane; round-robin "
                  "= mean of the two bracketing runs)",
        "settings": {k: v for k, v in s.items()},
        "shared_requests": shared_requests,
        "affinity": on,
        "round_robin_first": off_a,
        "round_robin_last": off_b,
        "round_robin": {"prefix_skip_rate": off_skip,
                        "tokens_per_s": off_tps},
        "monolithic": mono,
        "prefix_skip_rate_ratio":
            on["prefix_skip_rate"] / max(1e-9, off_skip),
        "tokens_per_s_ratio": on["tokens_per_s"] / max(1e-9, off_tps),
        "streams_bit_exact": True,
        "recompiles_after_warmup": recompiles,
        "platform": jax.default_backend(),
    }


def run_chaos_bench(s: dict) -> dict:
    """Fault-tolerant fleet serving under an injected replica crash.

    Two runs of one open-loop shared-prefix trace over the same
    2-replica fleet + shared host tier: a FAULT-FREE arm (empty-plan
    FaultClock — identical wiring, no faults) that doubles as the
    oracle, and a CHAOS arm that kills one replica MID-STREAM — the
    kill is armed through run_fleet's per-iteration hook the first
    time the victim is decoding with at least 3/4 of the trace
    submitted (late enough that eviction pressure has demoted whole
    prefix chains to the tier), so the victim dies holding live slots
    (not between arrivals, where recovery would have nothing to
    prove).  The health
    monitor must detect the death, salvage the victim's host-resident
    trie to the survivor, and re-admit every orphaned stream through
    the preemption-resume contract.  Hard-asserted, not reported:
    EVERY stream of the chaos arm — including the victim's orphans —
    is bit-exact with the fault-free arm, and neither arm recompiles
    after warmup.  Reported: the salvage rate (adopted / host-resident
    candidate tokens), recovery latency p50/p95 (virtual time:
    deterministic), and the orphan/readmission ledger."""
    from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

    config, params = _bench_model(s)
    trace, _ = build_fleet_workload(s)
    tier = s["shared_tier_bytes"]

    ref_clock = FaultClock(FaultPlan(seed=s["chaos_seed"]))
    ref = run_fleet(params, config, s, trace, fault_clock=ref_clock,
                    shared_tier_bytes=tier)
    if ref["replica_failures"]:
        raise RuntimeError(
            f"fault-free arm recorded failures "
            f"{ref['replica_failures']} — the empty plan injected "
            f"nothing, so the monitor false-positived")

    victim = s["chaos_victim"]
    plan = FaultPlan(seed=s["chaos_seed"])
    chaos_clock = FaultClock(plan)

    def arm_kill(fleet):
        if victim in plan.kills:
            return
        handle = fleet._handle(victim)
        if handle.state != "active":
            return
        if len(fleet._results) < (3 * len(trace)) // 4:
            return
        decoding = [sl for sl in handle.engine._slots
                    if sl.state == "decode" and len(sl.generated) >= 1]
        if decoding:
            plan.kill(victim,
                      at_step=chaos_clock._steps.get(victim, 0))

    chaos = run_fleet(params, config, s, trace, fault_clock=chaos_clock,
                      shared_tier_bytes=tier, on_step=arm_kill)
    kill_at = plan.kills.get(victim)
    if kill_at is None:
        raise RuntimeError(
            f"the kill never armed — {victim!r} was never observed "
            f"decoding after half the trace; the chaos trace needs "
            f"re-pacing")

    if not chaos["replica_failures"]:
        raise RuntimeError(
            f"planned kill of {victim!r} at step {kill_at} never "
            f"detected — the health monitor is blind")
    recompiles = ref["recompiles"] + chaos["recompiles"]
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup across the "
            f"chaos arms — recovery leaked a static shape")
    mismatched = [
        rid for rid, _, _, _ in trace
        if chaos["requests"][rid]["tokens"]
        != ref["requests"][rid]["tokens"]]
    if mismatched:
        raise RuntimeError(
            f"streams diverged between the chaos and fault-free arms "
            f"for {mismatched} — crash recovery is NOT bit-exact")
    incomplete = [
        rid for rid, _, max_new, _ in trace
        if len(chaos["requests"][rid]["tokens"]) != max_new]
    if incomplete:
        raise RuntimeError(
            f"streams {incomplete} did not run to their full budget "
            f"under chaos — orphan re-admission dropped tokens")
    for arm in (ref, chaos):
        arm.pop("requests")
    salvage_rate = (chaos["salvaged_prefix_tokens"]
                    / max(1, chaos["salvage_candidate_tokens"]))
    return {
        "suite": "serving-chaos",
        "metric": "bit-exact stream completion under an injected "
                  "replica kill (hard-asserted vs the fault-free arm), "
                  "with salvage rate and virtual-time recovery "
                  "latency alongside",
        "settings": {k: v for k, v in s.items()},
        "victim": victim,
        "kill_at_step": kill_at,
        "fault_free": ref,
        "chaos": chaos,
        "fault_events": [list(e) for e in chaos_clock.events[:8]],
        "streams_bit_exact": True,
        "streams_completed": len(trace),
        "salvage_rate": salvage_rate,
        "recovery_s": _percentiles(chaos["recovery_durations_s"]),
        "recompiles_after_warmup": recompiles,
        "tokens_per_s_ratio": (chaos["tokens_per_s"]
                               / max(1e-9, ref["tokens_per_s"])),
        "platform": jax.default_backend(),
    }


def run_rtc(params, config, s: dict, trace) -> dict:
    """Run-to-completion baseline: fixed worst-case shapes, batch
    barrier semantics.  One compiled prefill + one compiled decode scan,
    both at the workload's max bucket — the shapes a static server must
    provision (and the KV HBM it must reserve: num_slots x max_seq)."""
    from kubeshare_tpu.models.decoding import (
        greedy_decode_with_cache, prefill)

    batch = s["rtc_batch"]
    p_max = s["prompt_hi"]
    n_max = s["new_hi"]
    prefill_fn = jax.jit(lambda w, p: prefill(w, config, p))
    decode_fn = jax.jit(
        lambda w, cache, logits: greedy_decode_with_cache(
            w, config, cache, logits, n_max, prefill_length=p_max))
    # warmup at the (only) compiled shapes
    warm = jnp.zeros((batch, p_max), jnp.int32)
    cache, logits = prefill_fn(params, warm)
    jax.block_until_ready(decode_fn(params, cache, logits))
    compiles_before = (prefill_fn._cache_size(), decode_fn._cache_size())

    start = time.monotonic()
    queue = list(trace)
    ttfts, finishes = [], []
    useful = 0
    while queue:
        # the server is free: take up to `batch` ARRIVED requests (FIFO;
        # wait for the first if none has arrived yet)
        now = time.monotonic() - start
        if queue[0][3] > now:
            time.sleep(queue[0][3] - now)
            now = queue[0][3]
        group = [queue.pop(0)]
        while queue and len(group) < batch and queue[0][3] <= now:
            group.append(queue.pop(0))
        prompts = np.zeros((batch, p_max), np.int32)
        for i, (_, prompt, _, _) in enumerate(group):
            prompts[i, : prompt.size] = prompt  # padded to the max bucket
        cache, logits = prefill_fn(params, jnp.asarray(prompts))
        jax.block_until_ready(logits)
        prefill_done = time.monotonic() - start
        out = decode_fn(params, cache, logits)
        jax.block_until_ready(out)
        batch_done = time.monotonic() - start
        for rid, _, max_new, arrival in group:
            # a request's first token exists only once its batch's
            # prefill completes; it is not DONE until the whole batch
            # decodes to n_max (run-to-completion's defining cost)
            ttfts.append(prefill_done - arrival)
            finishes.append(batch_done - arrival)
            useful += max_new
    elapsed = time.monotonic() - start

    recompiles = (prefill_fn._cache_size() + decode_fn._cache_size()
                  - sum(compiles_before))
    per_token = [(f - t) / max(1, n_max - 1)
                 for f, t in zip(finishes, ttfts)]
    kv_bytes = (2 * config.n_layers * batch * config.kv_heads
                * config.max_seq_len * config.head_dim
                * jnp.dtype(config.dtype).itemsize)
    return {
        "tokens_per_s": useful / elapsed,
        "useful_tokens": useful,
        "elapsed_s": elapsed,
        "ttft_s": _percentiles(ttfts),
        "per_token_s": _percentiles(per_token),
        "kv_hbm_bytes_peak": kv_bytes,
        "recompiles": recompiles,
    }


def run_bench(s: dict) -> dict:
    config, params = _bench_model(s)
    # the comparison is KV-HBM-budgeted: both servers cache into the
    # same number of rows (paging turns the saved worst-case reservation
    # into extra concurrent slots)
    pool_rows = (s["num_blocks"] - 1) * s["block_size"]
    rtc_rows = s["rtc_batch"] * s["max_seq_len"]
    if pool_rows != rtc_rows:
        raise ValueError(
            f"continuous KV budget {pool_rows} rows != run-to-completion "
            f"budget {rtc_rows} — the equal-HBM comparison the docs "
            f"claim requires (num_blocks-1)*block_size == "
            f"rtc_batch*max_seq_len")
    trace = build_workload(s)

    # prefix cache OFF: this suite isolates the SCHEDULING win
    # (continuous batching vs batch barriers) per the methodology above;
    # --shared-prefix owns the cache-on comparison
    continuous = run_continuous(params, config, s, trace,
                                prefix_cache=False)
    continuous.pop("requests")  # per-request raw data: multi-tenant only
    rtc = run_rtc(params, config, s, trace)
    recompiles = continuous.pop("recompiles") + rtc.pop("recompiles")
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — a static-shape "
            f"leak; the comparison (and a TPU serving pod) is invalid")
    return {
        "suite": "serving",
        "metric": "continuous tokens/s over run-to-completion tokens/s "
                  "(same Poisson mixed-length trace, same KV-HBM budget; "
                  "useful tokens only)",
        "settings": {k: v for k, v in s.items()},
        "continuous": continuous,
        "run_to_completion": rtc,
        "ratio": continuous["tokens_per_s"] / rtc["tokens_per_s"],
        "kv_hbm_ratio": rtc["kv_hbm_bytes_peak"]
        / max(1, continuous["kv_hbm_bytes_peak"]),
        "recompiles_after_warmup": recompiles,
        "platform": jax.default_backend(),
    }


def run_shared_bench(s: dict) -> dict:
    """Prefix cache ON vs OFF on one shared-prefix trace: same engine
    geometry, same pool, same KV-HBM budget — the ratio isolates the
    radix cache (admission matching + CoW + LRU eviction) alone."""
    config, params = _bench_model(s)
    trace, sharers = build_shared_workload(s)

    cached = run_continuous(params, config, s, trace, prefix_cache=True)
    uncached = run_continuous(params, config, s, trace, prefix_cache=False)
    cached.pop("requests")
    uncached.pop("requests")
    recompiles = cached.pop("recompiles") + uncached.pop("recompiles")
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — a static-shape "
            f"leak; the comparison (and a TPU serving pod) is invalid")
    # what a perfect cache could have skipped: every sharer's prefix
    # tokens (the first sharer must always prefill cold)
    shared_prefix_tokens = len(sharers) * s["prefix_len"]
    skipped_fraction = (cached["prefix_hit_tokens"]
                        / max(1, shared_prefix_tokens))
    return {
        "suite": "serving-prefix",
        "metric": "prefix-cache-on tokens/s over prefix-cache-off "
                  "tokens/s (same shared-prefix Poisson trace, same "
                  "engine geometry and KV-HBM budget)",
        "settings": {k: v for k, v in s.items()},
        "shared_requests": len(sharers),
        "shared_prefix_tokens": shared_prefix_tokens,
        "cached": cached,
        "uncached": uncached,
        "ratio": cached["tokens_per_s"] / uncached["tokens_per_s"],
        "ttft_p50_ratio": uncached["ttft_s"]["p50"]
        / max(1e-9, cached["ttft_s"]["p50"]),
        "prefix_tokens_skipped_fraction": skipped_fraction,
        "recompiles_after_warmup": recompiles,
        "platform": jax.default_backend(),
    }


def run_mixed_bench(s: dict, aba: bool = True) -> dict:
    """Mixed batching ON vs OFF on one long-prompt/decode-mix trace:
    same engine geometry, same pool, same KV-HBM budget — the ratio
    isolates exactly what fusing a bounded prefill chunk into the
    decode dispatch buys.  The acceptance bar (full settings): TBT p99
    measurably LOWER with mixed on at equal-or-better aggregate
    tokens/s, every stream bit-exact between the two schedulers, zero
    recompiles after warmup.  ``aba=False`` drops the second bracketing
    unmixed run (tests lock mechanics, not timing — one run cheaper)."""
    config, params = _bench_model(s)
    trace, longs = build_mixed_workload(s)

    # ABA bracket: the FIRST trace run in a process pays one-time host
    # costs (allocator growth, page-cache faults) that would be
    # misattributed to whichever arm runs first — so the mixed run is
    # bracketed by two unmixed runs and compared against their mean.
    # Both unmixed runs emit identical streams and dispatch counts
    # (scheduling is deterministic); only wall time drifts.
    off_a = run_continuous(params, config, s, trace, mixed=False)
    on = run_continuous(params, config, s, trace, mixed=True)
    off_b = (run_continuous(params, config, s, trace, mixed=False)
             if aba else off_a)
    recompiles = (on.pop("recompiles") + off_a.pop("recompiles")
                  + (off_b.pop("recompiles") if aba else 0))
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — a static-shape "
            f"leak; the comparison (and a TPU serving pod) is invalid")
    # fused-dispatch correctness, end to end: the streams must be
    # IDENTICAL with and without mixed scheduling — fusing a prefill
    # chunk into the decode dispatch may not change a single token
    mismatched = [
        rid for rid in on["requests"]
        if on["requests"][rid]["tokens"] != off_a["requests"][rid]["tokens"]
        or on["requests"][rid]["tokens"] != off_b["requests"][rid]["tokens"]]
    if mismatched:
        raise RuntimeError(
            f"streams diverged between mixed and unmixed for "
            f"{mismatched} — the fused dispatch is NOT bit-exact")
    # the decode lanes whose tail the fused dispatch protects: TBT of
    # the short-prompt streamers, computed per arm from the metrics
    # plane (tbt_s above); per-request wall stats come from the records
    on.pop("requests")
    off_a.pop("requests")
    if aba:
        off_b.pop("requests")
    off_tps = (off_a["tokens_per_s"] + off_b["tokens_per_s"]) / 2
    off_p50 = (off_a["tbt_s"]["p50"] + off_b["tbt_s"]["p50"]) / 2
    off_p99 = (off_a["tbt_s"]["p99"] + off_b["tbt_s"]["p99"]) / 2
    return {
        "suite": "serving-mixed",
        "metric": "mixed-on tokens/s over mixed-off tokens/s and "
                  "time-between-tokens p50/p99 (same long-prompt/"
                  "decode-mix Poisson trace, same engine geometry and "
                  "KV-HBM budget; TBT read through the metrics plane; "
                  "unmixed = mean of the two bracketing runs)",
        "settings": {k: v for k, v in s.items()},
        "long_requests": len(longs),
        "mixed": on,
        "unmixed_first": off_a,
        "unmixed_last": off_b,
        "unmixed": {"tokens_per_s": off_tps,
                    "tbt_s": {"p50": off_p50, "p99": off_p99},
                    "mixed_steps": off_a["mixed_steps"]},
        "tokens_per_s_ratio": on["tokens_per_s"] / max(1e-9, off_tps),
        "tbt_p50_ratio": off_p50 / max(1e-9, on["tbt_s"]["p50"]),
        "tbt_p99_ratio": off_p99 / max(1e-9, on["tbt_s"]["p99"]),
        "streams_bit_exact": True,
        "recompiles_after_warmup": recompiles,
        "platform": jax.default_backend(),
    }


def run_loop_bench(s: dict, aba: bool = True) -> dict:
    """Device-resident multi-step loop ON (``steps_per_launch=K``) vs
    OFF (K=1) on one decode-heavy trace: same engine geometry, same
    pool, same KV-HBM budget — the comparison isolates what batching K
    scheduler iterations into one compiled launch buys.  The
    acceptance bar (full settings): host planner invocations per
    emitted token drop ~K x on the decode phase, every stream
    bit-exact between the two arms, zero recompiles after warmup.
    ``aba=False`` drops the second bracketing K=1 run (tests lock
    mechanics, not timing)."""
    config, params = _bench_model(s)
    trace = build_workload(s)
    k = s["steps_per_launch"]

    # ABA bracket: the first trace run in a process pays one-time host
    # costs that would otherwise be misattributed to whichever arm
    # runs first, and host_seconds is a WALL metric — so the loop run
    # is bracketed by two K=1 runs and compared against their mean
    off_a = run_continuous(params, config, s, trace)
    on = run_continuous(params, config, s, trace, steps_per_launch=k)
    off_b = (run_continuous(params, config, s, trace) if aba else off_a)
    recompiles = (on.pop("recompiles") + off_a.pop("recompiles")
                  + (off_b.pop("recompiles") if aba else 0))
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — a static-shape "
            f"leak; the comparison (and a TPU serving pod) is invalid")
    # the tentpole's correctness half, end to end: batching K
    # iterations into one launch may not change a single token
    mismatched = [
        rid for rid in on["requests"]
        if on["requests"][rid]["tokens"] != off_a["requests"][rid]["tokens"]
        or on["requests"][rid]["tokens"] != off_b["requests"][rid]["tokens"]]
    if mismatched:
        raise RuntimeError(
            f"streams diverged between K={k} and K=1 for {mismatched} "
            f"— the device-resident loop is NOT bit-exact")
    if on["loop_launches"] == 0:
        raise RuntimeError(
            "the device loop never fired — the trace is not "
            "decode-heavy enough to measure anything")
    on.pop("requests")
    off_a.pop("requests")
    if aba:
        off_b.pop("requests")
    off_planner = (off_a["planner_invocations"]
                   + off_b["planner_invocations"]) / 2
    off_host = (sum(off_a["host_seconds"].values())
                + sum(off_b["host_seconds"].values())) / 2
    on_host = sum(on["host_seconds"].values())
    off_tps = (off_a["tokens_per_s"] + off_b["tokens_per_s"]) / 2
    return {
        "suite": "serving-loop",
        "metric": "host planner invocations per emitted token at "
                  "steps_per_launch=K over K=1 (same decode-heavy "
                  "Poisson trace, same engine geometry and KV-HBM "
                  "budget; planner and host-seconds read through the "
                  "metrics plane; K=1 = mean of the two bracketing "
                  "runs)",
        "settings": {key: v for key, v in s.items()},
        "steps_per_launch": k,
        "loop": on,
        "unlooped_first": off_a,
        "unlooped_last": off_b,
        "unlooped": {"tokens_per_s": off_tps,
                     "planner_invocations": off_planner,
                     "planner_per_token": (off_a["planner_per_token"]
                                           + off_b["planner_per_token"])
                     / 2,
                     "host_seconds_total": off_host},
        "planner_invocations_ratio":
            off_planner / max(1, on["planner_invocations"]),
        "host_seconds_ratio": off_host / max(1e-9, on_host),
        "tokens_per_s_ratio": on["tokens_per_s"] / max(1e-9, off_tps),
        # units per launch actually realized, read off the metrics
        # plane's summary family (early exits pull it under K; a
        # decode-heavy trace should sit near K)
        "realized_fusion_depth":
            on["loop_realized_depth"]["sum"]
            / max(1, on["loop_realized_depth"]["count"]),
        "streams_bit_exact": True,
        "recompiles_after_warmup": recompiles,
        "platform": jax.default_backend(),
    }


def run_loop_spec_bench(s: dict, aba: bool = True) -> dict:
    """Verify-in-loop (device residency v2) vs the v1 device loop vs
    K=1, all three arms speculating on one echoed phrase-pool trace at
    the same engine geometry and KV-HBM budget.  The v1 arm runs the
    SAME engine with the speculative loop programs disarmed — every
    drafted round exits the device for a standalone verify span, the
    per-span planner bill the verify-in-loop fold exists to cut — so
    the headline ratio isolates exactly the fold.  The acceptance bar
    (full settings): host planner invocations per emitted token >= 2x
    lower than the v1 loop, realized fusion depth read off the metrics
    plane's summary family, every stream bit-exact across all arms
    (in-loop verification is exact-match against the engine's own pick
    policy — draft content only moves the acceptance RATE), zero
    recompiles after warmup everywhere.  ``aba=False`` drops the
    second bracketing v1 run (tests lock mechanics, not timing)."""
    config, params = _bench_model(s)
    trace = echo_spec_trace(params, config, s, build_spec_workload(s))
    k = s["steps_per_launch"]

    # ABA bracket: host_seconds is a WALL metric, so the v2 run is
    # bracketed by two v1-loop runs and compared to their mean;
    # planner-invocation counts are deterministic.  The trailing K=1
    # arm pins the no-loop oracle streams.
    v1_a = run_continuous(params, config, s, trace, speculative=True,
                          steps_per_launch=k, spec_loop=False)
    v2 = run_continuous(params, config, s, trace, speculative=True,
                        steps_per_launch=k,
                        admission_ring=s["admission_ring"])
    v1_b = (run_continuous(params, config, s, trace, speculative=True,
                           steps_per_launch=k, spec_loop=False)
            if aba else v1_a)
    flat = run_continuous(params, config, s, trace, speculative=True)
    recompiles = (v2.pop("recompiles") + v1_a.pop("recompiles")
                  + (v1_b.pop("recompiles") if aba else 0)
                  + flat.pop("recompiles"))
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — a static-shape "
            f"leak; the comparison (and a TPU serving pod) is invalid")
    # the tentpole's correctness half, end to end: folding draft +
    # verify + acceptance + ring admission into one resident launch
    # may not change a single token vs the v1 loop OR the K=1 engine
    arms = {"v1_loop": v1_a, "k1": flat}
    if aba:
        arms["v1_loop_last"] = v1_b
    mismatched = [
        (name, rid) for name, arm in arms.items()
        for rid in v2["requests"]
        if v2["requests"][rid]["tokens"] != arm["requests"][rid]["tokens"]]
    if mismatched:
        raise RuntimeError(
            f"streams diverged vs the verify-in-loop arm for "
            f"{mismatched} — the speculative device loop is NOT "
            f"bit-exact")
    if v2["spec_loop_launches"] == 0:
        raise RuntimeError(
            "the speculative device loop never fired — the trace is "
            "not draftable enough to measure anything")
    v2.pop("requests")
    for arm in arms.values():
        arm.pop("requests", None)
    useful = v2["useful_tokens"]
    v1_planner = (v1_a["planner_invocations"]
                  + v1_b["planner_invocations"]) / 2
    v1_host = (sum(v1_a["host_seconds"].values())
               + sum(v1_b["host_seconds"].values())) / 2
    v2_host = sum(v2["host_seconds"].values())
    flat_host = sum(flat["host_seconds"].values())
    v1_tps = (v1_a["tokens_per_s"] + v1_b["tokens_per_s"]) / 2
    drafted = sum(v2["spec_drafted"].values())
    accepted = sum(v2["spec_accepted"].values())
    depth = v2["loop_realized_depth"]
    return {
        "suite": "serving-loop-v2",
        "metric": "host planner invocations per emitted token, "
                  "verify-in-loop (spec loop + admission ring) over "
                  "the v1 device loop (drafted rounds verify outside "
                  "the loop) — same echoed phrase-pool closed-loop "
                  "trace, same engine geometry and KV-HBM budget; "
                  "planner, host-seconds, exit reasons and realized "
                  "depth all read through the metrics plane; v1 = "
                  "mean of the two bracketing runs; a K=1 arm pins "
                  "the no-loop oracle streams",
        "settings": {key: v for key, v in s.items()},
        "steps_per_launch": k,
        "admission_ring": s["admission_ring"],
        "loop_v2": v2,
        "loop_v1_first": v1_a,
        "loop_v1_last": v1_b,
        "unlooped": flat,
        "loop_v1": {"tokens_per_s": v1_tps,
                    "planner_invocations": v1_planner,
                    "planner_per_token": (v1_a["planner_per_token"]
                                          + v1_b["planner_per_token"])
                    / 2,
                    "host_seconds_total": v1_host},
        "planner_invocations_ratio_vs_v1":
            v1_planner / max(1, v2["planner_invocations"]),
        "planner_invocations_ratio_vs_k1":
            flat["planner_invocations"]
            / max(1, v2["planner_invocations"]),
        "host_seconds_per_token": {
            "v2": v2_host / max(1, useful),
            "v1": v1_host / max(1, useful),
            "k1": flat_host / max(1, useful)},
        "host_seconds_ratio_vs_v1": v1_host / max(1e-9, v2_host),
        "tokens_per_s_ratio_vs_v1":
            v2["tokens_per_s"] / max(1e-9, v1_tps),
        # realized depth straight off the metrics plane's summary
        # family (both loop kinds; redraft/retire exits pull it
        # under K, ring refills push launches back toward it)
        "realized_fusion_depth":
            depth["sum"] / max(1, depth["count"]),
        "loop_exit_reasons": v2["loop_exit_reasons"],
        "draft_acceptance_rate": accepted / max(1, drafted),
        "drafted_tokens": drafted,
        "accepted_tokens": accepted,
        "streams_bit_exact": True,
        "recompiles_after_warmup": recompiles,
        "platform": jax.default_backend(),
    }


def run_autotune_bench(s: dict, aba: bool = True) -> dict:
    """Cost-model-driven autotuner ON vs hand-set knobs on one
    three-phase shifting trace: identical engine geometry, identical
    pool and KV-HBM budget, identical hand-set starting values
    (``steps_per_launch``, ``hand_mixed_budget``, speculation on) —
    the tuned arm differs ONLY in ``autotune=True``, so the
    comparison isolates what online retuning of the recompile-free
    knob subset buys as the workload shifts under it.  Hard asserts:
    every stream bit-exact tuned vs both hand-set brackets (every
    knob is scheduling-only), zero recompiles after warmup in every
    arm (decisions confined to the warmed envelope).  Headline: the
    tuner matching or beating hand-set per-request latency on >= 2
    of the 3 phases, with the knob trajectory logged.  ``aba=False``
    drops the second bracketing hand-set run (tests lock mechanics,
    not timing)."""
    config, params = _bench_model(s)
    trace, phase_of = build_autotune_workload(s)
    common = dict(speculative=True,
                  steps_per_launch=s["steps_per_launch"],
                  mixed_prefill_budget=s["hand_mixed_budget"])

    # ABA bracket: first-run one-time host costs and wall-clock drift
    # must not be misattributed to either arm, so the tuned run is
    # bracketed by two hand-set runs and compared against their mean
    hand_a = run_continuous(params, config, s, trace, **common)
    tuned = run_continuous(params, config, s, trace, autotune=True,
                           **common)
    hand_b = (run_continuous(params, config, s, trace, **common)
              if aba else hand_a)
    recompiles = (tuned.pop("recompiles") + hand_a.pop("recompiles")
                  + (hand_b.pop("recompiles") if aba else 0))
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — the tuner "
            f"escaped the warmed envelope (or a static-shape leak); "
            f"the comparison (and a TPU serving pod) is invalid")
    # the sandbox contract's correctness half, end to end: retuning
    # scheduling knobs mid-serve may not change a single token
    mismatched = [
        rid for rid in tuned["requests"]
        if tuned["requests"][rid]["tokens"]
        != hand_a["requests"][rid]["tokens"]
        or tuned["requests"][rid]["tokens"]
        != hand_b["requests"][rid]["tokens"]]
    if mismatched:
        raise RuntimeError(
            f"streams diverged between tuned and hand-set for "
            f"{mismatched} — the autotuner is NOT scheduling-only")

    def phase_latency(arm):
        # mean per-request completion latency (finished - arrival, the
        # record is already arrival-relative) per workload phase
        acc = {}
        for rid, rec in arm["requests"].items():
            acc.setdefault(phase_of[rid], []).append(rec["finished_s"])
        return {ph: float(np.mean(v)) for ph, v in acc.items()}

    tuned_lat = phase_latency(tuned)
    hand_lat_a, hand_lat_b = phase_latency(hand_a), phase_latency(hand_b)
    phases = {}
    won = 0
    for ph in ("decode_heavy", "prefill_heavy", "draftable"):
        hand = (hand_lat_a[ph] + hand_lat_b[ph]) / 2
        ratio = hand / max(1e-9, tuned_lat[ph])
        # "matching or beating": within 10% of the hand-set arm counts
        # as a match — wall-clock on a shared CPU core is that noisy
        ok = tuned_lat[ph] <= hand * 1.10
        won += bool(ok)
        phases[ph] = {"tuned_latency_s": tuned_lat[ph],
                      "hand_latency_s": hand,
                      "latency_ratio_hand_over_tuned": ratio,
                      "matched_or_beat": ok}
    trajectory = tuned["tuner"]
    tuned.pop("requests")
    hand_a.pop("requests")
    if aba:
        hand_b.pop("requests")
    hand_tps = (hand_a["tokens_per_s"] + hand_b["tokens_per_s"]) / 2
    return {
        "suite": "serving-autotune",
        "metric": "per-phase mean request latency, cost-model "
                  "autotuner vs hand-set knobs (same three-phase "
                  "shifting Poisson trace, same engine geometry and "
                  "KV-HBM budget, same starting knob values; hand-set "
                  "= mean of the two bracketing runs)",
        "settings": {key: v for key, v in s.items()},
        "tuned": tuned,
        "hand_first": hand_a,
        "hand_last": hand_b,
        "phases": phases,
        "phases_matched_or_beaten": won,
        "knob_trajectory": trajectory["trajectory"],
        "tuner_decisions": trajectory["decisions"],
        "tokens_per_s_ratio": tuned["tokens_per_s"] / max(1e-9, hand_tps),
        "dispatches_per_token_ratio":
            (hand_a["dispatches_per_token"]
             + hand_b["dispatches_per_token"]) / 2
            / max(1e-9, tuned["dispatches_per_token"]),
        "streams_bit_exact": True,
        "recompiles_after_warmup": recompiles,
        "platform": jax.default_backend(),
    }


def run_disagg_bench(s: dict, aba: bool = True) -> dict:
    """Disaggregated prefill/decode vs the monolithic MIXED engine on
    one long-prefill/steady-decode adversarial trace at equal TOTAL
    KV-HBM budget ((prefill_blocks-1) + (decode_blocks-1) ==
    (mono_blocks-1) — asserted, the equal-budget claim is the whole
    comparison).  The monolithic arm runs with mixed batching ON — the
    strongest in-pool answer to the same traffic — so the ratio
    isolates what REMOVING prefill from the decode dispatch buys over
    merely bounding it.  The acceptance bar (full settings): decode
    TBT p99 strictly lower disagg-on at parity (>= 1.0x) aggregate
    tokens/s, every stream bit-exact across arms, zero recompiles
    after warmup in both pools.  ``aba=False`` drops the second
    bracketing monolithic run (tests lock mechanics, not timing)."""
    config, params = _bench_model(s)
    p_blocks = s["prefill_num_blocks"] - 1
    d_blocks = s["decode_num_blocks"] - 1
    mono_blocks = s["num_blocks"] - 1
    if p_blocks + d_blocks != mono_blocks:
        raise ValueError(
            f"disagg KV budget {p_blocks}+{d_blocks} blocks != "
            f"monolithic budget {mono_blocks} — the equal-HBM "
            f"comparison requires the split pools to sum to the "
            f"monolithic pool")
    trace, longs = build_mixed_workload(s)

    # ABA bracket (docs/perf.md methodology): first-trace-run host
    # costs bias whichever arm runs first, so the disagg run is
    # bracketed by two monolithic-mixed runs and compared to their
    # mean; monolithic streams and dispatch counts are deterministic —
    # only wall time drifts between A and B.
    off_a = run_continuous(params, config, s, trace, mixed=True)
    on = run_disagg(params, config, s, trace)
    off_b = (run_continuous(params, config, s, trace, mixed=True)
             if aba else off_a)
    recompiles = (on.pop("recompiles") + off_a.pop("recompiles")
                  + (off_b.pop("recompiles") if aba else 0))
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — a static-shape "
            f"leak; the comparison (and a TPU serving pod) is invalid")
    # handoff correctness, end to end: migrating a prompt's KV chain
    # between pools may not change a single token of any stream
    mismatched = [
        rid for rid in on["requests"]
        if on["requests"][rid]["tokens"] != off_a["requests"][rid]["tokens"]
        or on["requests"][rid]["tokens"] != off_b["requests"][rid]["tokens"]]
    if mismatched:
        raise RuntimeError(
            f"streams diverged between disagg and monolithic for "
            f"{mismatched} — the KV migration is NOT bit-exact")
    if on["migration"]["delivered"] != on["migration"]["packed"]:
        raise RuntimeError(
            f"{on['migration']['packed'] - on['migration']['delivered']} "
            f"migration(s) packed but never delivered after drain")
    on.pop("requests")
    off_a.pop("requests")
    if aba:
        off_b.pop("requests")
    off_tps = (off_a["tokens_per_s"] + off_b["tokens_per_s"]) / 2
    off_p50 = (off_a["tbt_s"]["p50"] + off_b["tbt_s"]["p50"]) / 2
    off_p99 = (off_a["tbt_s"]["p99"] + off_b["tbt_s"]["p99"]) / 2
    decode_tbt = on["tbt_by_pool_s"]["decode"]
    return {
        "suite": "serving-disagg",
        "metric": "decode-pool TBT p99 disagg-on vs monolithic-mixed "
                  "TBT p99 (same long-prefill/steady-decode Poisson "
                  "trace, same TOTAL KV-HBM budget split across the "
                  "pools; TBT read through the metrics plane's "
                  "pool-labeled histograms; monolithic = mean of the "
                  "two bracketing runs)",
        "settings": {k: v for k, v in s.items()},
        "long_requests": len(longs),
        "disagg": on,
        "monolithic_first": off_a,
        "monolithic_last": off_b,
        "monolithic": {"tokens_per_s": off_tps,
                       "tbt_s": {"p50": off_p50, "p99": off_p99},
                       "mixed_steps": off_a["mixed_steps"]},
        "tokens_per_s_ratio": on["tokens_per_s"] / max(1e-9, off_tps),
        "decode_tbt_p50_ratio": off_p50 / max(1e-9, decode_tbt["p50"]),
        "decode_tbt_p99_ratio": off_p99 / max(1e-9, decode_tbt["p99"]),
        "streams_bit_exact": True,
        "recompiles_after_warmup": recompiles,
        "platform": jax.default_backend(),
    }


def run_speculative_bench(s: dict, aba: bool = True) -> dict:
    """Speculative decoding ON vs OFF on one phrase-pool repetitive
    trace: same engine geometry, same pool, same KV-HBM budget — the
    ratio isolates what self-drafted verify chunks buy.  The headline
    is DISPATCH-denominated (CPU wall time misprices a TPU's verify
    chunk): target-model dispatches per emitted token, sequential vs
    speculative.  The acceptance bar (full settings): >= 1.3x fewer
    dispatches per token, every stream bit-identical to the sequential
    arm's (speculation's by-construction claim, hard-asserted), zero
    recompiles after warmup.  ``aba=False`` drops the second
    bracketing sequential run (tests lock mechanics, not timing)."""
    config, params = _bench_model(s)
    trace = echo_spec_trace(params, config, s, build_spec_workload(s))

    # ABA bracket: first-trace-run host costs (allocator growth,
    # page-cache faults) bias whichever arm runs first, so the
    # speculative run is bracketed by two sequential runs; dispatch
    # counts are deterministic — only wall time drifts between A and B
    off_a = run_continuous(params, config, s, trace, speculative=False)
    on = run_continuous(params, config, s, trace, speculative=True)
    off_b = (run_continuous(params, config, s, trace, speculative=False)
             if aba else off_a)
    recompiles = (on.pop("recompiles") + off_a.pop("recompiles")
                  + (off_b.pop("recompiles") if aba else 0))
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — a static-shape "
            f"leak; the comparison (and a TPU serving pod) is invalid")
    # speculation's defining property, end to end: exact-match
    # verification may not change a single token of any stream
    mismatched = [
        rid for rid in on["requests"]
        if on["requests"][rid]["tokens"] != off_a["requests"][rid]["tokens"]
        or on["requests"][rid]["tokens"] != off_b["requests"][rid]["tokens"]]
    if mismatched:
        raise RuntimeError(
            f"streams diverged between speculative and sequential for "
            f"{mismatched} — verify-span acceptance is NOT bit-exact")
    on.pop("requests")
    off_a.pop("requests")
    if aba:
        off_b.pop("requests")
    off_tps = (off_a["tokens_per_s"] + off_b["tokens_per_s"]) / 2
    drafted = sum(on["spec_drafted"].values())
    accepted = sum(on["spec_accepted"].values())
    return {
        "suite": "serving-speculative",
        "metric": "sequential dispatches-per-token over speculative "
                  "dispatches-per-token (same phrase-pool repetitive "
                  "closed-loop trace, same engine geometry and KV-HBM "
                  "budget; dispatches = decode spans + verify chunks, "
                  "one target-model forward pass each at decode_span "
                  "1; sequential = mean of the two bracketing runs — "
                  "their dispatch counts are identical by determinism)",
        "settings": {k: v for k, v in s.items()},
        "speculative": on,
        "sequential_first": off_a,
        "sequential_last": off_b,
        "sequential": {"tokens_per_s": off_tps,
                       "dispatches_per_token":
                           off_a["dispatches_per_token"]},
        "dispatches_per_token_ratio":
            off_a["dispatches_per_token"]
            / max(1e-9, on["dispatches_per_token"]),
        "tokens_per_s_ratio": on["tokens_per_s"] / max(1e-9, off_tps),
        "draft_acceptance_rate": accepted / max(1, drafted),
        "drafted_tokens": drafted,
        "accepted_tokens": accepted,
        "streams_bit_exact": True,
        "recompiles_after_warmup": recompiles,
        "platform": jax.default_backend(),
    }


def run_tiered_bench(s: dict, aba: bool = True) -> dict:
    """KV tiering on vs off with the device pool sized BELOW the
    shared-prefix working set, plus an HBM-sized reference pool:

    - **tier_off_a / tier_off_b**: the small pool, no host tier — the
      ABA bracket (first-trace-run host costs otherwise bias whichever
      arm runs first; docs/perf.md methodology).  Prefixes churn out of
      the pool between reuses and their prefill is paid again;
    - **tiered**: the SAME small pool with a host-RAM tier budgeted to
      hold the working set — evicted prefixes demote, reuses promote;
    - **hbm_sized**: a device pool big enough to keep every prefix
      cached — the skipped-token rate an HBM-sized cache achieves, the
      ceiling the tier should recover.

    Headline: the tiered arm's prefix-hit (skipped-token) rate
    recovering most of the HBM-sized arm's, TTFT p50 vs tiering off —
    with every stream hard-asserted identical across all arms and zero
    recompiles after warmup.  ``aba=False`` drops the second bracketing
    run (tests lock mechanics, not timing)."""
    config, params = _bench_model(s)
    trace, shared_tokens = build_tiered_workload(s)

    off_a = run_continuous(params, config, s, trace)
    tiered = run_continuous(params, config, s, trace,
                            host_tier_bytes=s["host_tier_bytes"])
    off_b = run_continuous(params, config, s, trace) if aba else off_a
    hbm = run_continuous(params, config, s, trace,
                         num_blocks=s["hbm_num_blocks"])
    recompiles = (off_a.pop("recompiles") + tiered.pop("recompiles")
                  + (off_b.pop("recompiles") if aba else 0)
                  + hbm.pop("recompiles"))
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — a static-shape "
            f"leak; the comparison (and a TPU serving pod) is invalid")
    # tier correctness end to end: demote/promote may not change ONE
    # token of any stream, at any pool size
    arms = {"tier_off_a": off_a, "tiered": tiered, "hbm_sized": hbm}
    if aba:
        arms["tier_off_b"] = off_b
    mismatched = [
        (name, rid) for name, arm in arms.items() if name != "tiered"
        for rid in tiered["requests"]
        if tiered["requests"][rid]["tokens"]
        != arm["requests"][rid]["tokens"]]
    if mismatched:
        raise RuntimeError(
            f"streams diverged vs the tiered arm for {mismatched} — "
            f"demote/promote is NOT bit-exact")
    for arm in arms.values():
        arm.pop("requests", None)
    # off_b IS off_a when aba=False, so the plain mean covers both modes
    off_hit = (off_a["prefix_hit_tokens"]
               + off_b["prefix_hit_tokens"]) / 2
    off_ttft = (off_a["ttft_s"]["p50"] + off_b["ttft_s"]["p50"]) / 2
    off_tps = (off_a["tokens_per_s"] + off_b["tokens_per_s"]) / 2
    # skipped-token rates against the whole shared-prefix token volume
    # (first touch of each prefix is necessarily cold in every arm)
    hit_rate_off = off_hit / max(1, shared_tokens)
    hit_rate_tiered = tiered["prefix_hit_tokens"] / max(1, shared_tokens)
    hit_rate_hbm = hbm["prefix_hit_tokens"] / max(1, shared_tokens)
    recovery = ((hit_rate_tiered - hit_rate_off)
                / max(1e-9, hit_rate_hbm - hit_rate_off))
    return {
        "suite": "serving-tier",
        "metric": "prefix-hit (skipped-token) rate with a host tier "
                  "under a device pool sized below the shared-prefix "
                  "working set, vs tiering off (ABA-bracketed) and vs "
                  "an HBM-sized pool (same many-prefix Poisson trace)",
        "settings": {k: v for k, v in s.items()},
        "shared_prefix_tokens": shared_tokens,
        "tiered": tiered,
        "tier_off_first": off_a,
        "tier_off_last": off_b,
        "tier_off": {"tokens_per_s": off_tps,
                     "ttft_p50_s": off_ttft,
                     "prefix_hit_tokens": off_hit},
        "hbm_sized": hbm,
        "hit_rate": {"tier_off": hit_rate_off,
                     "tiered": hit_rate_tiered,
                     "hbm_sized": hit_rate_hbm},
        "hit_recovery_vs_hbm": recovery,
        "ttft_p50_ratio": off_ttft
        / max(1e-9, tiered["ttft_s"]["p50"]),
        "tokens_per_s_ratio": tiered["tokens_per_s"]
        / max(1e-9, off_tps),
        "streams_bit_exact": True,
        "recompiles_after_warmup": recompiles,
        "platform": jax.default_backend(),
    }


def _serve_store_subprocess(store_path: str):
    """Spawn the prefix-store server as a genuinely separate PROCESS
    on a plain Python + numpy footprint: the child assembles a stub
    package skeleton and file-loads promtext/kv_tier/fabric directly,
    so the serving package __init__ (and jax behind it) never imports
    — asserted in the child.  Returns (proc, port); the server prints
    ``PORT <n>`` and then answers one connection's fetches."""
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import importlib.util, sys, types\n"
        "root, store = sys.argv[1], sys.argv[2]\n"
        "for name in ('kubeshare_tpu', 'kubeshare_tpu.utils',\n"
        "             'kubeshare_tpu.serving'):\n"
        "    pkg = types.ModuleType(name)\n"
        "    pkg.__path__ = [root + '/' + name.replace('.', '/')]\n"
        "    sys.modules[name] = pkg\n"
        "for name in ('kubeshare_tpu.utils.promtext',\n"
        "             'kubeshare_tpu.serving.kv_tier',\n"
        "             'kubeshare_tpu.serving.fabric'):\n"
        "    path = root + '/' + name.replace('.', '/') + '.py'\n"
        "    spec = importlib.util.spec_from_file_location(name, path)\n"
        "    mod = importlib.util.module_from_spec(spec)\n"
        "    sys.modules[name] = mod\n"
        "    spec.loader.exec_module(mod)\n"
        "assert 'jax' not in sys.modules, 'store server pulled in jax'\n"
        "sys.modules['kubeshare_tpu.serving.fabric']"
        ".serve_prefix_store(store)\n")
    proc = subprocess.Popen(
        [sys.executable, "-c", code, root, store_path],
        stdout=subprocess.PIPE, text=True)
    line = proc.stdout.readline()
    if not line.startswith("PORT "):
        proc.kill()
        raise RuntimeError(f"prefix-store server never bound: {line!r}")
    return proc, int(line.split()[1])


def run_fabric_bench(s: dict, aba: bool = True) -> dict:
    """Cluster KV fabric: cold prefixes promoted from DISK across a
    PROCESS boundary vs paying the cold prefill, at equal device KV
    budget:

    - **publish**: a publisher engine with a deliberately tiny pool +
      host tier primes the document corpus; the demotion cascade parks
      it on the mmap disk arena; ``export_prefix_store`` snapshots the
      trie (disk/host payloads preferred, live device blocks
      serialized on the fly) into one store file, served over TCP by a
      jax-free child process;
    - **fabric_off_a / fabric_off_b**: the cold engine, no adoption —
      the ABA bracket (docs/perf.md methodology); the first touch of
      every document pays its full prefill;
    - **fabric_on**: the SAME cold geometry, but before the first
      arrival a :class:`PrefixStoreClient` fetches every document's
      chain across the process boundary and ``adopt_into`` grafts it
      host-resident with ``origin="remote"`` — first touches become
      remote-origin tier hits.

    Headline: the fabric-on arm's prefix-hit (skipped-token) rate vs
    off and the remote-origin tier-hit split — with every stream
    hard-asserted identical across arms and zero recompiles after
    warmup.  ``aba=False`` drops the second bracketing run (tests lock
    mechanics, not timing)."""
    import tempfile

    from kubeshare_tpu.serving import (EngineConfig, PrefixStoreClient,
                                       Request, ServingEngine,
                                       export_prefix_store)
    from kubeshare_tpu.serving.fabric import prefix_fabric_key
    from kubeshare_tpu.serving.kv_tier import adopt_into

    config, params = _bench_model(s)
    docs, trace, shared_tokens = build_fabric_workload(s)

    workdir = tempfile.mkdtemp(prefix="kvfabric-")
    arena_path = os.path.join(workdir, "publisher.kvdisk")
    store_path = os.path.join(workdir, "prefixes.kvps")

    # --- publish: prime the corpus through the cascade, snapshot it
    publisher = ServingEngine(params, config, EngineConfig(
        num_slots=1, block_size=s["block_size"],
        num_blocks=s["publisher_num_blocks"],
        max_request_len=s["max_request_len"],
        prefill_chunk=s["prefill_chunk"],
        host_tier_bytes=s["publisher_host_tier_bytes"],
        disk_tier_bytes=s["disk_tier_bytes"],
        disk_tier_path=arena_path))
    publisher.warmup()
    for i, doc in enumerate(docs):
        publisher.submit(Request(f"pub{i}", doc, s["publisher_new"]))
        publisher.run()
        publisher.pop_finished()
    pub_metric = {(sm.name, tuple(sorted(sm.labels.items()))): sm.value
                  for f in publisher.collect_metrics()
                  for sm in f.samples}
    disk_demoted = int(_metric_value(
        pub_metric, "kubeshare_serving_disk_tier_blocks_total",
        event="demoted"))
    if disk_demoted <= 0:
        raise RuntimeError(
            "publisher cascade never reached the disk arena — the "
            "cross-process promotion would not be exercising the "
            "disk tier")

    def payload_of(node):
        if node.host_key is not None:
            e = publisher.host_tier.probe(node.host_key)
            return None if e is None else e.payload
        if node.disk_key is not None:
            return publisher.disk_tier.read(node.disk_key)
        if node.block is not None and node.block >= 0:
            return publisher._read_block_payload(node)
        return None

    manifest = export_prefix_store(publisher.prefix_index, payload_of,
                                   store_path)
    if not manifest:
        raise RuntimeError("publisher exported an empty prefix store")
    store_bytes = os.path.getsize(store_path)

    # --- serve it from another process, adopt into the fabric-on arm
    proc, port = _serve_store_subprocess(store_path)
    fetch_stats = {}

    def preload(engine):
        client = PrefixStoreClient(port)
        adopted_tokens = 0
        adopted_blocks = 0
        try:
            for doc in docs:
                aligned = (len(doc) // s["block_size"]) \
                    * s["block_size"]
                if not aligned:
                    continue
                chain = client.fetch(
                    prefix_fabric_key(doc[:aligned]))
                if not chain:
                    raise RuntimeError(
                        "store returned no chain for a published "
                        "document — the manifest and the corpus "
                        "disagree")
                for ctoks, payload in chain:
                    if adopt_into(engine.host_tier,
                                  engine.prefix_index, ctoks, payload,
                                  None, origin="remote") is not None:
                        adopted_blocks += 1
                matched = engine.prefix_match_len(doc[:aligned])
                adopted_tokens += int(matched)
        finally:
            fetch_stats.update(
                fetches=client.fetches, retries=client.retries,
                bytes_fetched=client.bytes_total,
                adopted_blocks=adopted_blocks,
                adopted_tokens=adopted_tokens)
            client.close()

    cold = dict(host_tier_bytes=s["host_tier_bytes"],
                disk_tier_bytes=s["disk_tier_bytes"])
    off_a = run_continuous(params, config, s, trace, **cold)
    on = run_continuous(params, config, s, trace, preload=preload,
                        **cold)
    off_b = run_continuous(params, config, s, trace, **cold) \
        if aba else off_a
    proc.stdout.close()
    proc.wait(timeout=30)
    publisher.disk_tier.close()
    import shutil
    shutil.rmtree(workdir, ignore_errors=True)

    recompiles = (off_a.pop("recompiles") + on.pop("recompiles")
                  + (off_b.pop("recompiles") if aba else 0))
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — a static-shape "
            f"leak; the comparison (and a TPU serving pod) is invalid")
    # fabric correctness end to end: bytes that crossed a disk arena, a
    # store file, and a process boundary may not change ONE token of
    # any stream
    arms = {"fabric_off_a": off_a, "fabric_on": on}
    if aba:
        arms["fabric_off_b"] = off_b
    mismatched = [
        (name, rid) for name, arm in arms.items() if name != "fabric_on"
        for rid in on["requests"]
        if on["requests"][rid]["tokens"]
        != arm["requests"][rid]["tokens"]]
    if mismatched:
        raise RuntimeError(
            f"streams diverged vs the fabric-on arm for {mismatched} — "
            f"remote promotion is NOT bit-exact")
    if on["tier_hit_origin"]["remote"] <= 0:
        raise RuntimeError(
            "fabric-on arm served zero remote-origin tier hits — the "
            "adopted chains never promoted")
    for arm in arms.values():
        arm.pop("requests", None)
    # off_b IS off_a when aba=False, so the plain mean covers both modes
    off_hit = (off_a["prefix_hit_tokens"]
               + off_b["prefix_hit_tokens"]) / 2
    off_ttft = (off_a["ttft_s"]["p50"] + off_b["ttft_s"]["p50"]) / 2
    off_tps = (off_a["tokens_per_s"] + off_b["tokens_per_s"]) / 2
    hit_rate_off = off_hit / max(1, shared_tokens)
    hit_rate_on = on["prefix_hit_tokens"] / max(1, shared_tokens)
    return {
        "suite": "serving-fabric",
        "metric": "prefix-hit (skipped-token) rate with cold documents "
                  "promoted from the publisher's disk arena across a "
                  "process boundary before the first arrival, vs the "
                  "same cold engine paying first-touch prefills "
                  "(ABA-bracketed, equal device KV budget)",
        "settings": {k: v for k, v in s.items()},
        "shared_document_tokens": shared_tokens,
        "store": {
            "chains": len(manifest),
            "bytes": store_bytes,
            "publisher_disk_demoted": disk_demoted,
            "publisher_disk_bytes_used": int(_metric_value(
                pub_metric, "kubeshare_serving_disk_tier_bytes",
                kind="used")),
        },
        "fetch": dict(fetch_stats),
        "fabric_on": on,
        "fabric_off_first": off_a,
        "fabric_off_last": off_b,
        "fabric_off": {"tokens_per_s": off_tps,
                       "ttft_p50_s": off_ttft,
                       "prefix_hit_tokens": off_hit},
        "hit_rate": {"fabric_off": hit_rate_off,
                     "fabric_on": hit_rate_on},
        "remote_tier_hits": on["tier_hit_origin"]["remote"],
        "ttft_p50_ratio": off_ttft
        / max(1e-9, on["ttft_s"]["p50"]),
        "tokens_per_s_ratio": on["tokens_per_s"]
        / max(1e-9, off_tps),
        "streams_bit_exact": True,
        "recompiles_after_warmup": recompiles,
        "platform": jax.default_backend(),
    }


def run_sharded_bench(s: dict, aba: bool = True) -> dict:
    """Tensor-parallel sharded serving vs the single-device engine on
    one long-prompt/decode-mix trace at equal PER-DEVICE KV-HBM
    budget: the head-sharded pool stores ``kv_heads/tp`` of every
    block per device, so at the same per-device bytes the tp-way arm
    funds ``tp x`` the allocatable blocks
    ((sharded_blocks-1) == tp * (mono_blocks-1) by construction).
    The acceptance bar: every stream bit-exact across arms (greedy
    mixed batching, full prefill chunks routed through the Ulysses
    re-shard), zero recompiles after warmup in BOTH engines, and the
    single-device arms' collective-bytes counters all zero.  On the
    forced host-CPU mesh the collectives are memcpys over one
    physical core set and per-device FLOPs do not shrink, so the
    tokens/s ratio is recorded as provenance, not a headline —
    dispatch counts, collective bytes, and the tp-x capacity are the
    portable numbers.  ``aba=False`` drops the second bracketing
    single-device run (tests lock mechanics, not timing)."""
    tp = s["tp"]
    if s["n_kv_heads"] < tp or s["n_kv_heads"] % tp:
        raise ValueError(
            f"the sharded bench locks the HEAD-SHARDED pool: "
            f"n_kv_heads {s['n_kv_heads']} must be a multiple of "
            f"tp={tp} (the replicated-KV fallback is test coverage, "
            f"not a capacity comparison)")
    config, params = _bench_model(s)
    mono_blocks = s["num_blocks"] - 1
    sharded_blocks = tp * mono_blocks  # same per-device KV bytes
    trace, longs = build_mixed_workload(s)

    # ABA bracket (docs/perf.md methodology): first-trace-run host
    # costs bias whichever arm runs first, so the sharded run is
    # bracketed by two single-device runs and compared to their mean;
    # streams and dispatch counts are deterministic — only wall time
    # drifts between A and B.
    off_a = run_continuous(params, config, s, trace, mixed=True)
    on = run_continuous(
        params, config, s, trace, mixed=True, tp=tp,
        num_blocks=sharded_blocks + 1,
        long_context_threshold=s.get("long_context_threshold"))
    off_b = (run_continuous(params, config, s, trace, mixed=True)
             if aba else off_a)
    recompiles = (on.pop("recompiles") + off_a.pop("recompiles")
                  + (off_b.pop("recompiles") if aba else 0))
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — a static-shape "
            f"leak; the comparison (and a TPU serving pod) is invalid")
    # the tentpole's whole claim, end to end: sharding the model and
    # the pool may not change a single token of any stream
    mismatched = [
        rid for rid in on["requests"]
        if on["requests"][rid]["tokens"] != off_a["requests"][rid]["tokens"]
        or on["requests"][rid]["tokens"] != off_b["requests"][rid]["tokens"]]
    if mismatched:
        raise RuntimeError(
            f"streams diverged between sharded and single-device for "
            f"{mismatched} — tensor-parallel execution is NOT bit-exact")
    if any(off_a["collective_bytes"].values()):
        raise RuntimeError(
            "single-device arm charged collective bytes "
            f"{off_a['collective_bytes']} — the estimate must be "
            "all-zero off-mesh")
    if not (on["collective_bytes"]["prefill_chunk"]
            and on["collective_bytes"]["decode_span"]):
        raise RuntimeError(
            f"sharded arm charged no collective traffic "
            f"{on['collective_bytes']} — the estimate is not wired "
            f"through the dispatch path")
    on.pop("requests")
    off_a.pop("requests")
    if aba:
        off_b.pop("requests")
    off_tps = (off_a["tokens_per_s"] + off_b["tokens_per_s"]) / 2
    off_p50 = (off_a["tbt_s"]["p50"] + off_b["tbt_s"]["p50"]) / 2
    off_p99 = (off_a["tbt_s"]["p99"] + off_b["tbt_s"]["p99"]) / 2
    return {
        "suite": "serving-sharded",
        "metric": "tp-way sharded engine vs single-device (mean of "
                  "the two bracketing runs) on the long-prompt/"
                  "decode-mix trace at equal per-device KV-HBM "
                  "budget; streams bit-exact; on a host-CPU mesh the "
                  "tokens/s ratio is provenance — dispatch counts, "
                  "collective bytes, and tp-x KV capacity are the "
                  "portable numbers",
        "settings": {k: v for k, v in s.items()},
        "tp": tp,
        "kv_blocks": {"single_device": mono_blocks,
                      "sharded_total": sharded_blocks,
                      "per_device_block_fraction": 1.0 / tp},
        "long_requests": len(longs),
        "sharded": on,
        "single_first": off_a,
        "single_last": off_b,
        "single": {"tokens_per_s": off_tps,
                   "tbt_s": {"p50": off_p50, "p99": off_p99},
                   "mixed_steps": off_a["mixed_steps"]},
        "tokens_per_s_ratio": on["tokens_per_s"] / max(1e-9, off_tps),
        "collective_bytes": on["collective_bytes"],
        "streams_bit_exact": True,
        "recompiles_after_warmup": recompiles,
        "devices": jax.device_count(),
        "platform": jax.default_backend(),
    }


def _tenant_stats(requests: dict, trace, tenant_of, tenant: str) -> dict:
    """Per-tenant aggregates over one run's raw request records:
    tokens/s over the tenant's active span (first arrival to last
    finish) plus TTFT percentiles."""
    mine = [(rid, max_new, arrival)
            for rid, _, max_new, arrival in trace
            if tenant_of[rid] == tenant]
    useful = sum(max_new for _, max_new, _ in mine)
    first_arrival = min(arrival for _, _, arrival in mine)
    last_finish = max(arrival + requests[rid]["finished_s"]
                      for rid, _, arrival in mine)
    ttfts = [requests[rid]["ttft_s"] for rid, _, _ in mine]
    return {
        "useful_tokens": useful,
        "span_s": last_finish - first_arrival,
        "tokens_per_s": useful / max(1e-9, last_finish - first_arrival),
        "ttft_s": _percentiles(ttfts),
    }


def run_qos_bench(s: dict) -> dict:
    """Multi-tenant QoS comparison at ONE shared KV-HBM budget:

    - **isolated**: the Guarantee tenant's trace alone — its entitled
      service level;
    - **qos_on**: Guarantee + Opportunistic flood with the QoS subsystem
      (class-priority fair queue, flood quota'd to half the pool,
      cache-backed preemption);
    - **qos_off**: the same merged trace through the single-tenant FIFO
      engine — what PR 1-2 serving does under the same flood.

    The acceptance criteria: under the flood the Guarantee tenant keeps
    >= 80% of its isolated tokens/s and its TTFT p50 degrades < 2x,
    while AGGREGATE throughput stays within 10% of the QoS-off run;
    every request's stream is bit-exact across qos_on/qos_off (preempted
    requests resume via the prefix cache); zero recompiles after warmup.
    """
    from kubeshare_tpu.serving import (QOS_OPPORTUNISTIC, TenantRegistry,
                                       TenantSpec)

    config, params = _bench_model(s)
    trace, tenant_of = build_qos_workload(s)
    g_trace = [e for e in trace if tenant_of[e[0]] == "prod"]

    def registry():
        return TenantRegistry([
            TenantSpec("prod"),
            TenantSpec("batch", qos_class=QOS_OPPORTUNISTIC,
                       kv_block_quota=s["o_quota_blocks"]),
        ])

    isolated = run_continuous(params, config, s, g_trace,
                              registry=registry(), tenant_of=tenant_of)
    qos_on = run_continuous(params, config, s, trace,
                            registry=registry(), tenant_of=tenant_of)
    qos_off = run_continuous(params, config, s, trace)
    recompiles = (isolated.pop("recompiles") + qos_on.pop("recompiles")
                  + qos_off.pop("recompiles"))
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — a static-shape "
            f"leak; the comparison (and a TPU serving pod) is invalid")
    # preemption correctness, end to end: the greedy streams must be
    # IDENTICAL with and without QoS scheduling — a preempted request's
    # cache-backed resume may not change a single token
    mismatched = [
        rid for rid in qos_on["requests"]
        if qos_on["requests"][rid]["tokens"]
        != qos_off["requests"][rid]["tokens"]]
    if mismatched:
        raise RuntimeError(
            f"streams diverged between qos_on and qos_off for "
            f"{mismatched} — preemption resume is NOT bit-exact")
    iso_req = isolated.pop("requests")
    on_req = qos_on.pop("requests")
    off_req = qos_off.pop("requests")
    iso_g = _tenant_stats(iso_req, g_trace, tenant_of, "prod")
    on_g = _tenant_stats(on_req, trace, tenant_of, "prod")
    on_o = _tenant_stats(on_req, trace, tenant_of, "batch")
    off_g = _tenant_stats(off_req, trace, tenant_of, "prod")
    return {
        "suite": "serving-qos",
        "metric": "Guarantee tenant retention under an Opportunistic "
                  "flood (same merged trace, same KV-HBM budget): "
                  "qos_on guarantee tokens/s over isolated, TTFT p50 "
                  "ratio, and aggregate qos_on/qos_off tokens/s",
        "settings": {k: v for k, v in s.items()},
        "isolated_guarantee": iso_g,
        "qos_on": qos_on,
        "qos_on_guarantee": on_g,
        "qos_on_opportunistic": on_o,
        "qos_off": qos_off,
        "qos_off_guarantee": off_g,
        "guarantee_retention": on_g["tokens_per_s"]
        / max(1e-9, iso_g["tokens_per_s"]),
        "guarantee_ttft_p50_ratio": on_g["ttft_s"]["p50"]
        / max(1e-9, iso_g["ttft_s"]["p50"]),
        "qos_off_guarantee_ttft_p50_ratio": off_g["ttft_s"]["p50"]
        / max(1e-9, iso_g["ttft_s"]["p50"]),
        "aggregate_ratio": qos_on["tokens_per_s"]
        / max(1e-9, qos_off["tokens_per_s"]),
        "preemptions": qos_on["preemptions"],
        "streams_bit_exact": True,
        "recompiles_after_warmup": recompiles,
        "platform": jax.default_backend(),
    }


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="seconds-fast tiny-model CPU path")
    parser.add_argument("--shared-prefix", action="store_true",
                        help="prefix-cache on/off comparison on a "
                             "shared-prefix trace")
    parser.add_argument("--multi-tenant", action="store_true",
                        help="QoS comparison: Guarantee tenant + "
                             "Opportunistic flood at one KV-HBM budget")
    parser.add_argument("--mixed", action="store_true",
                        help="stall-free mixed batching on/off on a "
                             "long-prompt/decode-mix trace")
    parser.add_argument("--tiered", action="store_true",
                        help="host-RAM KV tier on/off with the device "
                             "pool sized below the shared-prefix "
                             "working set, vs an HBM-sized pool")
    parser.add_argument("--speculative", action="store_true",
                        help="self-drafting speculative decoding on/off "
                             "on a phrase-pool repetitive trace "
                             "(dispatches-per-token headline)")
    parser.add_argument("--disagg", action="store_true",
                        help="disaggregated prefill/decode pools vs the "
                             "monolithic mixed engine at equal total "
                             "KV-HBM budget (decode TBT p99 headline)")
    parser.add_argument("--sharded", action="store_true",
                        help="tensor-parallel sharded engine vs "
                             "single-device at equal per-device KV "
                             "budget (streams hard-asserted identical; "
                             "dispatch/collective-bytes headline)")
    parser.add_argument("--device-loop", action="store_true",
                        help="device-resident multi-step loop "
                             "(steps_per_launch=K) vs K=1 on a "
                             "decode-heavy trace (streams hard-asserted "
                             "identical; planner-invocations-per-token "
                             "headline); combine with --speculative "
                             "for the verify-in-loop + admission-ring "
                             "suite (v2 vs v1 loop vs K=1 on an echoed "
                             "phrase-pool trace)")
    parser.add_argument("--fabric", action="store_true",
                        help="cluster KV fabric: cold documents "
                             "promoted from a publisher's disk arena "
                             "across a process boundary (jax-free "
                             "store server) vs paying first-touch "
                             "prefills, ABA-bracketed at equal device "
                             "KV budget (streams hard-asserted "
                             "identical; cold-start prefix-hit rate "
                             "and remote tier-hit headline)")
    parser.add_argument("--fleet", action="store_true",
                        help="replica fleet: prefix-affinity routing vs "
                             "round-robin at equal aggregate KV budget "
                             "(streams hard-asserted identical vs the "
                             "monolithic engine; aggregate prefix-skip "
                             "rate headline)")
    parser.add_argument("--chaos", action="store_true",
                        help="fault-tolerant fleet serving: kill a "
                             "replica mid-trace and hard-assert every "
                             "stream completes bit-exact vs the "
                             "fault-free arm (salvage rate and "
                             "recovery-latency headline)")
    parser.add_argument("--autotune", action="store_true",
                        help="cost-model autotuner vs hand-set knobs on "
                             "a three-phase shifting workload (streams "
                             "hard-asserted identical, zero recompiles; "
                             "per-phase latency headline, knob "
                             "trajectory logged)")
    parser.add_argument("--json", help="write the result JSON here too")
    args = parser.parse_args()
    if args.sharded and "host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # four virtual CPU devices to stand up the tp=4 serving mesh;
        # the flag must land before the first backend use
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")
    elif args.disagg and "host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # two virtual CPU devices so the pools' dispatches genuinely
        # overlap (virtual_multislice placement); the flag must land
        # before the first backend use, which is inside the run
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2")
    if args.chaos:
        result = run_chaos_bench(
            chaos_smoke_settings() if args.smoke else chaos_settings())
    elif args.autotune:
        result = run_autotune_bench(
            autotune_smoke_settings() if args.smoke
            else autotune_settings())
    elif args.fabric:
        result = run_fabric_bench(
            fabric_smoke_settings() if args.smoke else fabric_settings())
    elif args.fleet:
        result = run_fleet_bench(
            fleet_smoke_settings() if args.smoke else fleet_settings())
    elif args.sharded:
        result = run_sharded_bench(
            sharded_smoke_settings() if args.smoke else sharded_settings())
    elif args.disagg:
        result = run_disagg_bench(
            disagg_smoke_settings() if args.smoke else disagg_settings())
    elif args.device_loop and args.speculative:
        result = run_loop_spec_bench(
            loop_spec_smoke_settings() if args.smoke
            else loop_spec_settings())
    elif args.speculative:
        result = run_speculative_bench(
            spec_smoke_settings() if args.smoke else spec_settings())
    elif args.tiered:
        result = run_tiered_bench(
            tiered_smoke_settings() if args.smoke else tiered_settings())
    elif args.device_loop:
        result = run_loop_bench(
            loop_smoke_settings() if args.smoke else loop_settings())
    elif args.mixed:
        result = run_mixed_bench(
            mixed_smoke_settings() if args.smoke else mixed_settings())
    elif args.multi_tenant:
        result = run_qos_bench(
            qos_smoke_settings() if args.smoke else qos_settings())
    elif args.shared_prefix:
        result = run_shared_bench(
            shared_smoke_settings() if args.smoke else shared_settings())
    else:
        result = run_bench(
            smoke_settings() if args.smoke else default_settings())
    text = json.dumps(result, indent=2)
    print(text)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
    if args.chaos:
        ch = result["chaos"]
        rec = result["recovery_s"]
        print(f"\nchaos fleet (kill {result['victim']} mid-stream at "
              f"its step {result['kill_at_step']}): "
              f"{result['streams_completed']}/{result['streams_completed']} "
              f"streams completed BIT-EXACT vs the fault-free arm "
              f"(hard-asserted); cause "
              f"{list(ch['replica_failures'].keys())}; "
              f"{ch['orphans_readmitted']} orphaned streams "
              f"re-admitted on the survivor; "
              f"salvage rate {100 * result['salvage_rate']:.1f}% "
              f"({ch['salvaged_prefix_tokens']}/"
              f"{ch['salvage_candidate_tokens']} host-resident tokens "
              f"adopted); recovery p50 {1e3 * rec['p50']:.2f} ms / "
              f"p95 {1e3 * rec['p95']:.2f} ms (virtual time); "
              f"tokens/s ratio {result['tokens_per_s_ratio']:.3f}; "
              f"zero recompiles both arms", file=sys.stderr)
        return
    if args.autotune:
        ph = result["phases"]
        marks = " ".join(
            f"{name}={p['latency_ratio_hand_over_tuned']:.2f}x"
            f"{'*' if p['matched_or_beat'] else ''}"
            for name, p in ph.items())
        moves = result["knob_trajectory"]
        print(f"\nautotuner vs hand-set knobs on a shifting workload: "
              f"{result['phases_matched_or_beaten']}/3 phases matched "
              f"or beaten (target >= 2; per-phase hand/tuned latency "
              f"{marks}, * = within 10% or better); tokens/s ratio "
              f"{result['tokens_per_s_ratio']:.3f}; dispatches/token "
              f"ratio {result['dispatches_per_token_ratio']:.2f}x; "
              f"{len(moves)} knob moves "
              f"({', '.join(sorted(set(m[1] for m in moves))) or 'none'}); "
              f"decisions {result['tuner_decisions']}; streams "
              f"bit-exact; zero recompiles in every arm",
              file=sys.stderr)
        return
    if args.fabric:
        st, fe, hr = result["store"], result["fetch"], result["hit_rate"]
        print(f"\ncluster KV fabric ({st['chains']} chains / "
              f"{st['bytes']} store bytes published off a disk arena "
              f"holding {st['publisher_disk_demoted']} demoted blocks, "
              f"served by a jax-free child process): "
              f"{fe['adopted_blocks']} blocks "
              f"({fe['adopted_tokens']} document tokens) fetched over "
              f"TCP in {fe['fetches']} fetches / "
              f"{fe['bytes_fetched']} bytes and adopted "
              f"origin=remote; cold-start prefix-hit rate "
              f"{100 * hr['fabric_on']:.1f}% fabric-on vs "
              f"{100 * hr['fabric_off']:.1f}% fabric-off "
              f"(ABA-bracketed, equal device KV budget); "
              f"{result['remote_tier_hits']} remote-origin tier hits; "
              f"TTFT p50 ratio {result['ttft_p50_ratio']:.2f}x; "
              f"tokens/s ratio {result['tokens_per_s_ratio']:.3f}; "
              f"streams bit-exact across all arms; zero recompiles "
              f"after warmup", file=sys.stderr)
        return
    if args.fleet:
        on, rr = result["affinity"], result["round_robin"]
        mix = on["routing_decisions"]
        print(f"\nreplica fleet ({on['replicas']} replicas x "
              f"{on['kv_blocks_per_replica']} KV blocks == monolithic "
              f"budget): aggregate prefix-skip rate "
              f"{100 * on['prefix_skip_rate']:.1f}% affinity vs "
              f"{100 * rr['prefix_skip_rate']:.1f}% round-robin "
              f"({result['prefix_skip_rate_ratio']:.2f}x, target > 1x); "
              f"routing mix affinity={mix.get('affinity', 0)} "
              f"least_loaded={mix.get('least_loaded', 0)} "
              f"spill={mix.get('spill', 0)}; tokens/s ratio "
              f"{result['tokens_per_s_ratio']:.3f}; streams bit-exact "
              f"across all arms incl. monolithic; zero recompiles "
              f"after warmup", file=sys.stderr)
        return
    if args.sharded:
        on = result["sharded"]
        coll = result["collective_bytes"]
        kvb = result["kv_blocks"]
        print(f"\ntensor-parallel serving (tp={result['tp']}, host-CPU "
              f"mesh): {kvb['sharded_total']} allocatable KV blocks vs "
              f"{kvb['single_device']} single-device at the SAME "
              f"per-device bytes ({result['tp']}x capacity); tokens/s "
              f"ratio {result['tokens_per_s_ratio']:.3f} (provenance "
              f"only on CPU — collectives are memcpys, per-device "
              f"FLOPs don't shrink); {on['prefill_chunks']} prefill "
              f"chunks / {on['decode_steps']} decode spans / "
              f"{on['mixed_steps']} fused dispatches; collective "
              f"bytes prefill {coll['prefill_chunk']} / decode "
              f"{coll['decode_span']} / verify {coll['verify_span']}; "
              f"streams bit-exact; zero recompiles after warmup",
              file=sys.stderr)
        return
    if args.disagg:
        on, off = result["disagg"], result["monolithic"]
        mig = on["migration"]
        print(f"\ndisaggregated prefill/decode: decode-pool TBT p99 "
              f"{1e3 * on['tbt_by_pool_s']['decode']['p99']:.1f} ms vs "
              f"{1e3 * off['tbt_s']['p99']:.1f} ms monolithic-mixed "
              f"({result['decode_tbt_p99_ratio']:.2f}x lower, target "
              f"> 1x on the full workload); tokens/s ratio "
              f"{result['tokens_per_s_ratio']:.3f} (target >= 1.0); "
              f"{mig['delivered']}/{mig['packed']} chains migrated "
              f"({mig['migrated_bytes'] / 1024:.0f} KiB wire, staging "
              f"stall p99 {1e3 * mig['stall_s']['p99']:.2f} ms); "
              f"{on['prefill_chunks']} prefill chunks / "
              f"{on['decode_steps']} decode spans vs "
              f"{off['mixed_steps']} fused monolithic dispatches; "
              f"streams bit-exact", file=sys.stderr)
        return
    if args.device_loop and args.speculative:
        v2 = result["loop_v2"]
        k = result["steps_per_launch"]
        hspt = result["host_seconds_per_token"]
        exits = {r: n for r, n in
                 sorted(result["loop_exit_reasons"].items()) if n}
        print(f"\nverify-in-loop device loop (K={k}, admission ring "
              f"{result['admission_ring']}): planner invocations/token "
              f"{v2['planner_per_token']:.3f} vs "
              f"{result['loop_v1']['planner_per_token']:.3f} v1-loop "
              f"({result['planner_invocations_ratio_vs_v1']:.2f}x "
              f"fewer, target >= 2x on the full workload; "
              f"{result['planner_invocations_ratio_vs_k1']:.2f}x vs "
              f"K=1); host s/token {hspt['v2']:.2e} vs "
              f"{hspt['v1']:.2e} v1 "
              f"({result['host_seconds_ratio_vs_v1']:.2f}x lower); "
              f"realized fusion depth "
              f"{result['realized_fusion_depth']:.1f}/{k} (metrics "
              f"plane); {v2['spec_loop_launches']} spec-loop launches, "
              f"exits {exits}; draft acceptance "
              f"{100 * result['draft_acceptance_rate']:.1f}%; tokens/s "
              f"ratio {result['tokens_per_s_ratio_vs_v1']:.3f} vs v1; "
              f"streams bit-exact across v2/v1/K=1; zero recompiles",
              file=sys.stderr)
        return
    if args.speculative:
        on = result["speculative"]
        print(f"\nspeculative decoding: "
              f"{result['sequential']['dispatches_per_token']:.3f} "
              f"sequential dispatches/token vs "
              f"{on['dispatches_per_token']:.3f} speculative "
              f"({result['dispatches_per_token_ratio']:.2f}x fewer, "
              f"target >= 1.3x on the full workload); draft acceptance "
              f"{100 * result['draft_acceptance_rate']:.1f}% "
              f"({result['accepted_tokens']}/{result['drafted_tokens']} "
              f"tokens); {on['verify_steps']} verify chunks "
              f"({on['mixed_verify_steps']} fused with prefill); "
              f"tokens/s ratio {result['tokens_per_s_ratio']:.3f}; "
              f"streams bit-exact", file=sys.stderr)
        return
    if args.tiered:
        hr = result["hit_rate"]
        tier = result["tiered"]["tier"]
        print(f"\nkv tiering under a pool ~1/2 the prefix working set: "
              f"skipped-token rate {hr['tiered']:.3f} vs "
              f"{hr['tier_off']:.3f} off / {hr['hbm_sized']:.3f} "
              f"HBM-sized ("
              f"{100 * result['hit_recovery_vs_hbm']:.0f}% of the "
              f"HBM-sized cache's advantage recovered, target >= 50%); "
              f"TTFT p50 {result['ttft_p50_ratio']:.2f}x lower than "
              f"off; tokens/s ratio {result['tokens_per_s_ratio']:.3f}; "
              f"{tier['demoted']} demotions, {tier['promoted']} "
              f"promotions, {tier['dropped']} drops, "
              f"{1e3 * tier['promotion_stall_s']:.1f} ms promotion "
              f"stall; streams bit-exact", file=sys.stderr)
        return
    if args.device_loop:
        on, off = result["loop"], result["unlooped"]
        k = result["steps_per_launch"]
        print(f"\ndevice loop (K={k}): planner invocations/token "
              f"{on['planner_per_token']:.3f} vs "
              f"{off['planner_per_token']:.3f} at K=1 "
              f"({result['planner_invocations_ratio']:.2f}x fewer, "
              f"target ~{k}x on the decode phase); host seconds "
              f"{result['host_seconds_ratio']:.2f}x lower; realized "
              f"fusion depth {result['realized_fusion_depth']:.1f}/{k}; "
              f"tokens/s ratio {result['tokens_per_s_ratio']:.3f}; "
              f"{on['loop_launches']} launches; streams bit-exact",
              file=sys.stderr)
        return
    if args.mixed:
        on, off = result["mixed"], result["unmixed"]
        print(f"\nmixed batching: TBT p99 "
              f"{1e3 * on['tbt_s']['p99']:.1f} ms vs "
              f"{1e3 * off['tbt_s']['p99']:.1f} ms unmixed "
              f"({result['tbt_p99_ratio']:.2f}x lower, target > 1x on "
              f"the full workload); TBT p50 "
              f"{result['tbt_p50_ratio']:.2f}x lower; tokens/s ratio "
              f"{result['tokens_per_s_ratio']:.3f} (target >= 1.0); "
              f"{on['mixed_steps']} fused dispatches; streams bit-exact",
              file=sys.stderr)
        return
    if args.multi_tenant:
        print(f"\nguarantee retention under flood: "
              f"{result['guarantee_retention']:.3f} (target >= 0.8); "
              f"guarantee TTFT p50 ratio: "
              f"{result['guarantee_ttft_p50_ratio']:.2f}x (target < 2x, "
              f"qos-off was "
              f"{result['qos_off_guarantee_ttft_p50_ratio']:.2f}x); "
              f"aggregate qos-on/qos-off: "
              f"{result['aggregate_ratio']:.3f} (target >= 0.9); "
              f"preemptions: {result['preemptions']}; streams bit-exact",
              file=sys.stderr)
        return
    ratio = result["ratio"]
    if args.shared_prefix:
        print(f"\nprefix-cache on/off tokens/s ratio: {ratio:.3f} "
              f"(target >= 1.3 on the full workload); "
              f"{100 * result['prefix_tokens_skipped_fraction']:.1f}% of "
              f"shared-prefix tokens skipped (target >= 50%)",
              file=sys.stderr)
    else:
        print(f"\ncontinuous/run-to-completion tokens/s ratio: {ratio:.3f} "
              f"(target >= 1.5 on the full workload)", file=sys.stderr)


if __name__ == "__main__":
    main()
