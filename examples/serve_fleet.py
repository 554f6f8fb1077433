"""Replica-fleet serving walkthrough: scheduler-placed replicas,
prefix-affinity routing, a mid-run scale-up, and a drain whose cache
the survivors inherit.

The cluster-scale serving shape (and serve_disagg's sequel): instead
of one engine growing tp/disagg features, the `dp` axis multiplies
whole engines —

  - a :class:`ReplicaFleet` (`serving/fleet.py`): N engines behind one
    submit/step/run surface, arrivals routed by LONGEST CACHED PREFIX
    (`PrefixAffinityPolicy` probing each replica's radix trie),
    least-loaded breaking ties, QoS and saturation spills tempering
    affinity;
  - a :class:`FleetPlacementPlane` (`scheduler/placement.py`): every
    replica rendered as a pod carrying the ``sharedgpu/*``
    fractional-cell labels and pushed through the REAL KubeShare
    Filter/Score/Reserve cycle — the binding (node, cell, vGPU uuid)
    read back from the post-bind annotations, cells reclaimed through
    the pod-deleted path at retirement;
  - online elasticity: ``scale_up()`` builds, places, and warms a new
    replica with ZERO recompiles on the others; ``drain()`` stops a
    replica's arrivals, lets it finish, then demotes its ENTIRE radix
    trie into the fleet's shared host tier so surviving replicas
    promote the retiree's cached prefixes instead of re-prefilling
    them.

Run (no TPU needed; the cluster is in-memory, the engines are real):

    JAX_PLATFORMS=cpu python -m examples.serve_fleet

Cache-aware routing has no cell in `BENCHMARK.json` yet (`ROADMAP.md`
2.16): nothing about it is measured on the chip.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from kubeshare_tpu.utils.compile_cache import configure_compile_cache

configure_compile_cache()

import jax.numpy as jnp
import numpy as np

TOPOLOGY = """
cellTypes:
  V4-NODE:
    childCellType: "TPU-v4"
    childCellNumber: 4
    childCellPriority: 60
    isNodeLevel: true
  3-V4-NODE:
    childCellType: V4-NODE
    childCellNumber: 3
cells:
- cellType: 3-V4-NODE
  cellChildren:
  - cellId: host-a
  - cellId: host-b
  - cellId: host-c
"""


def main() -> None:
    from kubeshare_tpu import constants
    from kubeshare_tpu.cell import load_config
    from kubeshare_tpu.cell.allocator import ChipInfo
    from kubeshare_tpu.cluster.api import FakeClock, Node
    from kubeshare_tpu.cluster.fake import FakeCluster
    from kubeshare_tpu.models.transformer import (TransformerConfig,
                                                  transformer_init)
    from kubeshare_tpu.scheduler import (FleetPlacementPlane,
                                         KubeShareScheduler, SchedulerArgs,
                                         SchedulerEngine)
    from kubeshare_tpu.serving import EngineConfig, ReplicaFleet, Request

    print("=== 1. model + per-replica geometry ===")
    config = TransformerConfig(
        d_model=256, n_layers=2, n_heads=8, n_kv_heads=2, d_ff=1024,
        vocab_size=8000, max_seq_len=192, dtype=jnp.float32,
        positional="rope", attention="reference")
    params = transformer_init(jax.random.PRNGKey(0), config)
    ec = EngineConfig(num_slots=3, block_size=16, num_blocks=33,
                      max_request_len=160, prefill_chunk=32)
    print(f"each replica: {ec.num_slots} slots, {ec.num_blocks - 1} "
          f"allocatable KV blocks x {ec.block_size} tokens")

    print("=== 2. control plane: 3 TPU nodes, the real scheduler ===")
    hbm = 32 << 30
    nodes = ("host-a", "host-b", "host-c")
    inventory = {
        node: [ChipInfo(f"{node}-tpu-{i}", hbm, "TPU-v4", i, (i, rank, 0))
               for i in range(4)]
        for rank, node in enumerate(nodes)}
    cluster = FakeCluster()
    for n in nodes:
        cluster.add_node(Node(
            name=n, labels={constants.NODE_LABEL_FILTER: "true"}))
    clock = FakeClock(1000.0)
    plugin = KubeShareScheduler(
        topology=load_config(text=TOPOLOGY), cluster=cluster,
        inventory=lambda node: inventory.get(node, []),
        args=SchedulerArgs(), clock=clock)
    plane = FleetPlacementPlane(
        SchedulerEngine(plugin, cluster, clock), cluster,
        gpu_request="0.5", gpu_limit="0.5", gpu_memory=1 << 30,
        priority=10)

    print("=== 3. fleet of 2, every replica a scheduled pod ===")
    fleet = ReplicaFleet(params, config, ec, replicas=2,
                         max_replicas=3, placement=plane,
                         shared_tier_bytes=4 << 20)
    for h in fleet.replicas:
        p = h.placement
        print(f"{h.name}: pod {p.pod_name} bound on {p.node}, "
              f"cell {p.cell_id}, vGPU {p.gpu_uuid}")
        if p.cell_id == "":
            raise RuntimeError(f"{h.name} bound without a cell")
    fleet.warmup()
    baseline = fleet.compile_counts()

    print("=== 4. shared-prefix traffic, routed by affinity ===")
    rng = np.random.default_rng(7)
    families = {name: rng.integers(0, config.vocab_size, 48)
                for name in ("legal", "chat", "code")}

    def member(fam, i, max_new=8):
        tail = rng.integers(0, config.vocab_size,
                            int(rng.integers(6, 15)))
        return Request(f"{fam}{i}", np.concatenate(
            [families[fam], tail]), max_new)

    start = time.monotonic()
    tokens = 0
    # one opener per family warms a trie somewhere...
    for fam in families:
        fleet.submit(member(fam, 0))
    tokens += sum(len(r.tokens) for r in fleet.run().values())
    # ...and every later family member should chase its cache
    for i in (1, 2):
        for fam in families:
            fleet.submit(member(fam, i))
        tokens += sum(len(r.tokens) for r in fleet.run().values())
    owners = {fam: {fleet.owner_of(f"{fam}{i}") for i in range(3)}
              for fam in families}
    for fam, reps in sorted(owners.items()):
        print(f"family {fam!r}: all {3} requests on {sorted(reps)}")
        if len(reps) != 1:
            raise RuntimeError(
                f"family {fam!r} scattered across {sorted(reps)} — "
                f"affinity routing broke")
    print(f"routing decisions so far: {fleet.routing_decisions}")

    print("=== 5. scale up: third replica placed + warmed online ===")
    h3 = fleet.scale_up()
    p3 = h3.placement
    print(f"{h3.name}: pod {p3.pod_name} bound on {p3.node}, "
          f"cell {p3.cell_id}")
    baseline = fleet.compile_counts()  # +1 replica's warmup programs
    for fam in families:
        fleet.submit(member(fam, 3))
    tokens += sum(len(r.tokens) for r in fleet.run().values())

    print("=== 6. drain: the retiree's cache outlives it ===")
    victim = fleet.owner_of("legal0")
    survivor_names = [h.name for h in fleet.replicas
                      if h.name != victim and h.state == "active"]
    before = {n: fleet._handle(n).engine.prefix_match_len(
        families["legal"]) for n in survivor_names}
    fleet.drain(victim)
    fleet.run()      # finishes in-flight work, then hands the trie over
    if fleet._handle(victim).state != "retired":
        raise RuntimeError(f"{victim} never retired after drain")
    if cluster.get_pod(plane.namespace, f"fleet-{victim}") is not None:
        raise RuntimeError(f"{victim}'s pod survived its retirement")
    inherited = {n: fleet._handle(n).engine.prefix_match_len(
        families["legal"]) for n in survivor_names}
    print(f"'legal' prefix visible on survivors: {before} tokens "
          f"before drain -> {inherited} after (host-tier handoff)")
    if max(inherited.values()) < 32:
        raise RuntimeError(
            f"survivors inherited only {inherited} tokens of the "
            f"retiree's 48-token prefix")
    # a post-drain family member promotes the inherited blocks
    fleet.submit(member("legal", 4))
    tokens += sum(len(r.tokens) for r in fleet.run().values())
    heir = fleet.owner_of("legal4")
    hits = fleet._handle(heir).engine.tier_hit_requests
    print(f"legal4 routed to {heir}, tier hits there: {hits}")
    if hits < 1:
        raise RuntimeError(
            "the follow-up request never promoted the inherited cache")
    elapsed = time.monotonic() - start

    print("=== 7. the fleet's merged metrics plane ===")
    metric = {(s.name, tuple(sorted(s.labels.items()))): s.value
              for f in fleet.collect_metrics() for s in f.samples}

    def total(name, **want):
        return sum(v for (n, labels), v in metric.items()
                   if n == name and all(
                       dict(labels).get(k) == w for k, w in want.items()))

    states = {st: int(total("kubeshare_serving_fleet_replicas", state=st))
              for st in ("active", "draining", "retired")}
    hit_tokens = int(total("kubeshare_serving_prefix_hit_tokens_total"))
    print(f"replicas by state: {states}; scale events: "
          f"up={int(total('kubeshare_serving_fleet_scale_events_total', direction='up'))} "
          f"down={int(total('kubeshare_serving_fleet_scale_events_total', direction='down'))}; "
          f"drains observed: "
          f"{int(total('kubeshare_serving_fleet_drain_seconds_count'))}")
    print(f"routing: affinity="
          f"{int(total('kubeshare_serving_fleet_routing_decisions_total', reason='affinity'))} "
          f"least_loaded="
          f"{int(total('kubeshare_serving_fleet_routing_decisions_total', reason='least_loaded'))} "
          f"spill="
          f"{int(total('kubeshare_serving_fleet_routing_decisions_total', reason='spill'))}; "
          f"prefix tokens skipped: {hit_tokens}")
    recompiles = sum(fleet.compile_counts().values()) - sum(
        baseline.values())
    print(f"aggregate: {tokens} tokens in {elapsed:.2f} s "
          f"({tokens / elapsed:.0f} tok/s); recompiles after "
          f"warmup/scale-up: {recompiles}")
    if states != {"active": 2, "draining": 0, "retired": 1}:
        raise RuntimeError(f"unexpected fleet state {states}")
    if hit_tokens <= 0:
        raise RuntimeError("affinity routing never skipped a prefix")
    if recompiles:
        raise RuntimeError(
            f"{recompiles} recompilations after warmup — static-shape "
            f"leak in a replica")
    print("fleet demo complete")


if __name__ == "__main__":
    main()
