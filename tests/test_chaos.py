"""Fault-injection suite: the serving plane under seeded chaos.

Every scenario here runs a real serving stack with a
:class:`~kubeshare_tpu.serving.chaos.FaultPlan` wired through the
chaos seams (no monkeypatching) and pins the recovery contract's
strongest form: the streams a chaos run emits are BIT-EXACT with the
fault-free run — greedy and sampled, through replica kills, hung
dispatches, dropped migration tickets, rotted tier bytes, and
transient tokend refusals.  Determinism is asserted too: replaying
the same plan over the same trace yields the same faults, fault for
fault, and the same streams.
"""

import socket
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models.transformer import transformer_init

from chaos_helpers import _PinFirst, _fleet, _metric, _mixed_trace
from serving_helpers import _small_config

pytestmark = [pytest.mark.serving, pytest.mark.chaos]


class TestFaultPlan:
    def test_builders_validate_and_chain(self):
        from kubeshare_tpu.serving.chaos import FaultPlan

        plan = (FaultPlan(seed=7).kill("r1", at_step=4)
                .slow_dispatch("r0", at=2, seconds=0.5)
                .corrupt_tier_put(3).drop_ticket(0).refuse_tokend(2))
        assert plan.kills == {"r1": 4}
        assert plan.slow == {"r0": {2: 0.5}}
        assert plan.tier_corruptions == {3}
        assert plan.ticket_drops == {0}
        assert plan.tokend_refusals == {2}
        for bad in (lambda p: p.kill("x", -1),
                    lambda p: p.slow_dispatch("x", -1, 1.0),
                    lambda p: p.slow_dispatch("x", 0, 0.0),
                    lambda p: p.corrupt_tier_put(-1),
                    lambda p: p.drop_ticket(-1),
                    lambda p: p.refuse_tokend(-1)):
            with pytest.raises(ValueError):
                bad(FaultPlan())

    def test_corruption_is_seeded_length_preserving_and_detected(self):
        """The bit flip derives from (seed, ordinal): same plan rots
        the same bit on replay, a different seed rots a different one,
        and the wire crc catches either."""
        from kubeshare_tpu.serving import WireCorruption, pack_block, \
            unpack_block
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        k = np.ones((2, 2, 4, 8), np.float32)
        payload = pack_block([1, 2, 3, 4], k, k)

        def rot(seed):
            clock = FaultClock(FaultPlan(seed=seed).corrupt_tier_put(0))
            return clock.on_tier_put(payload)

        a, b, c = rot(3), rot(3), rot(4)
        assert a == b and a != c and len(a) == len(payload)
        unpack_block(payload)  # pristine round-trips
        with pytest.raises(WireCorruption):
            unpack_block(a)
        # untargeted ordinals pass through untouched
        clock = FaultClock(FaultPlan(seed=3).corrupt_tier_put(5))
        assert clock.on_tier_put(payload) == payload

    def test_virtual_clock_and_ordinal_counters(self):
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        clock = FaultClock(FaultPlan(), step_dt=0.25)

        class Eng:
            replica_label = "r9"

        assert clock.now() == 0.0
        clock.on_engine_step(Eng())
        clock.on_engine_step(Eng())
        assert clock.now() == 0.5
        clock.advance(1.0)
        assert clock.now() == 1.5
        with pytest.raises(ValueError):
            clock.advance(-1.0)


class TestReplicaKillRecovery:
    def test_kill_mid_trace_bit_exact_greedy_and_sampled(self):
        """The tentpole contract: kill a replica mid-trace and every
        stream — greedy and sampled, including the dead replica's
        orphans — matches the fault-free fleet run token for token,
        with zero recompiles on the survivor."""
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)

        def run_arm(fault_clock=None):
            fleet = _fleet(params, config, top_k=10, top_p=0.95,
                           shared_tier_bytes=1 << 20,
                           fault_clock=fault_clock)
            fleet.warmup()
            base = fleet.compile_counts()
            for r in _mixed_trace():
                fleet.submit(r)
            streams = {k: v.tokens for k, v in fleet.run().items()}
            return fleet, base, streams

        _, _, want = run_arm()
        clock = FaultClock(FaultPlan(seed=7).kill("r1", at_step=2))
        fleet, base, got = run_arm(clock)
        assert got == want
        assert fleet.replica_failures == {"liveness": 1}
        assert fleet._handle("r1").state == "failed"
        assert fleet._handle("r1").fail_cause == "liveness"
        assert fleet.orphans_readmitted > 0
        # zero recompiles on every SURVIVING replica
        after = fleet.compile_counts()
        for k, v in base.items():
            if not k.startswith("r1"):
                assert after[k] == v, k
        # the failure is visible through the metrics plane
        fams = fleet.collect_metrics()
        assert _metric(fams, "kubeshare_serving_fleet_replica_failures_total",
                       cause="liveness") == 1
        assert _metric(fams,
                       "kubeshare_serving_fleet_recovery_seconds_count") == 1
        assert _metric(fams, "kubeshare_serving_fleet_replicas",
                       state="failed") == 1

    def test_replay_same_plan_same_faults_same_streams(self):
        """Replayability is the chaos harness's own invariant: two runs
        of one plan over one trace agree fault-for-fault and
        token-for-token."""
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)

        def run_once():
            clock = FaultClock(FaultPlan(seed=7).kill("r1", at_step=3))
            fleet = _fleet(params, config, shared_tier_bytes=1 << 20,
                           fault_clock=clock)
            fleet.warmup()
            for r in _mixed_trace():
                fleet.submit(r)
            return clock.events, {k: v.tokens
                                  for k, v in fleet.run().items()}

        events_a, streams_a = run_once()
        events_b, streams_b = run_once()
        assert events_a == events_b
        assert streams_a == streams_b
        assert any(e[0] == "kill" for e in events_a)

    def test_orphan_lands_on_survivor_with_salvaged_prefix(self):
        """The dead replica's host-resident trie is salvage: the
        survivor adopts it through the SHARED tier, the orphan resumes
        there mid-stream, and the stream still matches the dense
        reference."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        plan = FaultPlan(seed=11)
        clock = FaultClock(plan)
        fleet = _fleet(params, config, num_slots=2, num_blocks=13,
                       max_request_len=32, routing=_PinFirst(),
                       shared_tier_bytes=1 << 20, fault_clock=clock)
        fleet.warmup()
        rng = np.random.default_rng(13)
        shared = rng.integers(0, 64, 16)
        fleet.submit(Request(
            "warm", np.concatenate([shared, rng.integers(0, 64, 4)]), 4))
        fleet.run()
        owner = fleet.owner_of("warm")
        oeng = fleet._handle(owner).engine
        # eviction pressure demotes the warm prefix to the shared tier
        for i in range(3):
            fleet.submit(Request(f"p{i}", rng.integers(0, 64, 20), 4))
            fleet.run()
        assert oeng.tier_demoted_blocks > 0
        survivor = [h for h in fleet.replicas if h.name != owner][0]
        # an in-flight request on the doomed replica, killed mid-decode
        prompt = np.concatenate([shared, rng.integers(0, 64, 4)])
        fleet.submit(Request("orphan", prompt, 10))
        while True:
            slots = [s for s in oeng._slots
                     if s.rid == "orphan" and s.state == "decode"]
            if slots and len(slots[0].generated) >= 2:
                break
            assert fleet.step(), "fleet idle before the orphan decoded"
        plan.kill(owner, at_step=clock._steps.get(owner, 0))
        out = fleet.run()
        ref = np.asarray(greedy_decode(
            params, config, jnp.asarray(prompt, jnp.int32)[None], 10))[0]
        assert out["orphan"].tokens == list(ref)
        assert fleet.owner_of("orphan") == survivor.name
        assert fleet.salvaged_tokens > 0
        assert survivor.engine.prefix_match_len(shared) >= 16
        fams = fleet.collect_metrics()
        assert _metric(
            fams,
            "kubeshare_serving_fleet_salvaged_prefix_tokens_total") > 0
        assert _metric(
            fams, "kubeshare_serving_fleet_orphans_readmitted_total") >= 1


class TestPlacementReclaim:
    TOPOLOGY = """
cellTypes:
  V4-NODE:
    childCellType: "TPU-v4"
    childCellNumber: 4
    childCellPriority: 60
    isNodeLevel: true
  2-V4-NODE:
    childCellType: V4-NODE
    childCellNumber: 2
cells:
- cellType: 2-V4-NODE
  cellChildren:
  - cellId: host-a
  - cellId: host-b
"""

    def test_crash_releases_cell_through_pod_deleted_path(self):
        """A killed replica's fractional cell is reclaimed exactly as a
        retirement's would be — through the placement plane's
        pod-deleted path — and the release-cause ledger says it was a
        crash, not planned churn."""
        from kubeshare_tpu import constants
        from kubeshare_tpu.cell import load_config
        from kubeshare_tpu.cell.allocator import ChipInfo
        from kubeshare_tpu.cluster.api import FakeClock, Node
        from kubeshare_tpu.cluster.fake import FakeCluster
        from kubeshare_tpu.scheduler import (FleetPlacementPlane,
                                             KubeShareScheduler,
                                             SchedulerArgs, SchedulerEngine)
        from kubeshare_tpu.serving import EngineConfig, ReplicaFleet, \
            Request
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        hbm = 32 << 30
        inventory = {
            node: [ChipInfo(f"{node}-tpu-{i}", hbm, "TPU-v4", i,
                            (i, rank, 0)) for i in range(4)]
            for rank, node in enumerate(("host-a", "host-b"))
        }
        cluster = FakeCluster()
        for n in ("host-a", "host-b"):
            cluster.add_node(Node(
                name=n, labels={constants.NODE_LABEL_FILTER: "true"}))
        sched_clock = FakeClock(1000.0)
        plugin = KubeShareScheduler(
            topology=load_config(text=self.TOPOLOGY), cluster=cluster,
            inventory=lambda node: inventory.get(node, []),
            args=SchedulerArgs(), clock=sched_clock)
        engine = SchedulerEngine(plugin, cluster, sched_clock)
        plane = FleetPlacementPlane(engine, cluster, gpu_request="0.5",
                                    gpu_limit="0.5", gpu_memory=1 << 30,
                                    priority=10)

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        clock = FaultClock(FaultPlan(seed=5).kill("r1", at_step=1))
        fleet = ReplicaFleet(
            params, config,
            EngineConfig(num_slots=3, block_size=4, num_blocks=21,
                         max_request_len=48, prefill_chunk=8),
            replicas=2, placement=plane, fault_clock=clock)
        assert len(cluster.list_pods(namespace="serving")) == 2
        fleet.warmup()
        rng = np.random.default_rng(3)
        for i in range(4):
            fleet.submit(Request(f"q{i}", rng.integers(0, 64, 10), 4))
        out = fleet.run()
        assert fleet.replica_failures == {"liveness": 1}
        assert all(len(r.tokens) == 4 for r in out.values())
        # the dead replica's pod went through the pod-deleted reclaim
        assert len(cluster.list_pods(namespace="serving")) == 1
        assert plane.release_causes == {"liveness": 1}


class TestWatchdog:
    def _decode_dispatch_ordinal(self, fleet, clock, label, rid):
        """Park the target request in decode, then report the label's
        NEXT dispatch ordinal so planned delays land deterministically."""
        eng = fleet._handle(label).engine
        while True:
            slots = [s for s in eng._slots
                     if s.rid == rid and s.state == "decode"]
            if slots and len(slots[0].generated) >= 1:
                return clock._dispatches.get(label, 0)
            assert fleet.step(), "fleet idle before target decoded"

    def test_slow_dispatch_below_budget_is_not_a_failure(self):
        """A merely-slow replica must NOT be declared dead: repeated
        dispatches inside the budget never trip the watchdog."""
        from kubeshare_tpu.serving import Request
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        plan = FaultPlan(seed=3)
        clock = FaultClock(plan)
        fleet = _fleet(params, config, routing=_PinFirst(),
                       fault_clock=clock, watchdog_budget_s=0.05,
                       watchdog_grace=2)
        fleet.warmup()
        rng = np.random.default_rng(17)
        fleet.submit(Request("slowpoke", rng.integers(0, 64, 10), 12))
        n = self._decode_dispatch_ordinal(fleet, clock, "r0", "slowpoke")
        for k in range(4):  # slow but under budget, four steps running
            plan.slow_dispatch("r0", n + k, 0.02)
        out = fleet.run()
        assert fleet.replica_failures == {}
        assert fleet._handle("r0").state == "active"
        assert len(out["slowpoke"].tokens) == 12
        # at least one planned delay actually landed (step fusion may
        # finish the stream in fewer dispatches than tokens)
        assert sum(1 for e in clock.events if e[0] == "slow_dispatch") >= 1

    def test_hung_dispatch_trips_watchdog_and_stream_survives(self):
        """A hung replica makes 'progress' every step — only the clock
        catches it.  Consecutive over-budget steps hit the grace limit,
        the replica is failed with cause=watchdog, and its in-flight
        stream completes bit-exact on the survivor."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        plan = FaultPlan(seed=3)
        clock = FaultClock(plan)
        fleet = _fleet(params, config, routing=_PinFirst(),
                       shared_tier_bytes=1 << 20, fault_clock=clock,
                       watchdog_budget_s=0.05, watchdog_grace=2)
        fleet.warmup()
        rng = np.random.default_rng(19)
        prompt = rng.integers(0, 64, 10)
        fleet.submit(Request("victim", prompt, 12))
        n = self._decode_dispatch_ordinal(fleet, clock, "r0", "victim")
        for k in range(4):  # hung: every dispatch blows the budget
            plan.slow_dispatch("r0", n + k, 10.0)
        out = fleet.run()
        assert fleet.replica_failures == {"watchdog": 1}
        assert fleet._handle("r0").fail_cause == "watchdog"
        ref = np.asarray(greedy_decode(
            params, config, jnp.asarray(prompt, jnp.int32)[None], 12))[0]
        assert out["victim"].tokens == list(ref)
        fams = fleet.collect_metrics()
        assert _metric(fams, "kubeshare_serving_fleet_replica_failures_total",
                       cause="watchdog") == 1
        # recovery latency includes the hang: at least the two
        # over-budget steps of virtual time
        assert _metric(fams,
                       "kubeshare_serving_fleet_recovery_seconds_sum") >= 20.0


class TestTierCorruption:
    def test_rotted_tier_bytes_are_a_loud_miss_not_wrong_tokens(self):
        """Corrupt EVERY byte-payload the shared tier stores: the
        survivor's promotion path must detect each rotted block
        (crc32), fall back to re-prefill, and still emit the exact
        dense streams — corruption costs latency, never correctness."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import Request
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        plan = FaultPlan(seed=23)
        for n in range(200):
            plan.corrupt_tier_put(n)
        clock = FaultClock(plan)
        fleet = _fleet(params, config, shared_tier_bytes=1 << 20,
                       fault_clock=clock)
        fleet.warmup()
        rng = np.random.default_rng(11)
        shared = rng.integers(0, 64, 16)
        fleet.submit(Request(
            "seed", np.concatenate([shared, rng.integers(0, 64, 4)]), 4))
        fleet.run()
        owner = fleet.owner_of("seed")
        survivor = [h for h in fleet.replicas if h.name != owner][0]
        fleet.drain(owner)
        fleet.run()
        # the retiree's trie reached the tier — rotted
        assert len(fleet.shared_tier._entries) > 0
        assert any(e[0] == "corrupt_put" for e in clock.events)
        prompt = np.concatenate([shared, rng.integers(0, 64, 4)])
        fleet.submit(Request("heir", prompt, 6))
        out = fleet.run()
        ref = np.asarray(greedy_decode(
            params, config, jnp.asarray(prompt, jnp.int32)[None], 6))[0]
        assert out["heir"].tokens == list(ref)
        assert survivor.engine.tier_corrupt_blocks > 0
        fams = fleet.collect_metrics()
        assert _metric(
            fams, "kubeshare_serving_tier_corruptions_total") > 0


class TestDisaggHandoffTTL:
    PREFILL = dict(num_slots=2, block_size=4, num_blocks=17,
                   max_request_len=48, prefill_chunk=8, mixed=False)
    DECODE = dict(num_slots=3, block_size=4, num_blocks=25,
                  max_request_len=48, prefill_chunk=8, mixed=False)

    def _router(self, params, config, **kwargs):
        from kubeshare_tpu.serving import DisaggRouter, EngineConfig

        return DisaggRouter(params, config, EngineConfig(**self.PREFILL),
                            EngineConfig(**self.DECODE), **kwargs)

    def _trace(self):
        rng = np.random.default_rng(61)
        return [dict(rid="long", prompt=rng.integers(0, 64, 29),
                     max_new_tokens=6),
                dict(rid="s0", prompt=rng.integers(0, 64, 5),
                     max_new_tokens=8),
                dict(rid="samp", prompt=rng.integers(0, 64, 11),
                     max_new_tokens=7, temperature=0.8,
                     rng=jax.random.PRNGKey(62))]

    def _mono_streams(self, params, config):
        from kubeshare_tpu.serving import EngineConfig, Request, \
            ServingEngine

        mono = ServingEngine(params, config, EngineConfig(
            num_slots=3, block_size=4, num_blocks=41, max_request_len=48,
            prefill_chunk=8, mixed=False))
        mono.warmup()
        for r in self._trace():
            mono.submit(Request(**r))
        return {k: v.tokens for k, v in mono.run().items()}

    def test_dropped_ticket_expires_releases_reserve_and_stays_exact(self):
        """The reserve-leak regression: a ticket whose deliveries keep
        dropping must EXPIRE — releasing its decode reserve (the
        admission gate counts pending tickets) and resuming the request
        through prefill-from-cache — instead of wedging the router.
        Streams stay bit-exact through drop, retry, expiry, and
        resume; the retry ledger tells the story."""
        from kubeshare_tpu.serving import Request
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        config = _small_config(n_kv_heads=2, positional="rope")
        params = transformer_init(jax.random.PRNGKey(0), config)
        want = self._mono_streams(params, config)

        plan = FaultPlan(seed=9)
        for n in (0, 1, 2):
            plan.drop_ticket(n)
        router = self._router(params, config, handoff_ttl_steps=3,
                              handoff_backoff_steps=1)
        router.fault_clock = FaultClock(plan)
        router.warmup()
        base = router.compile_counts()
        for r in self._trace():
            router.submit(Request(**r))
        got = {k: v.tokens for k, v in router.run().items()}
        assert got == want
        # reserve gauge back to baseline: no ticket left holding slots
        assert len(router._tickets) == 0
        assert router.handoff_retries["dropped"] == 3
        assert router.handoff_retries["expired"] >= 1
        assert router.compile_counts() == base
        fams = router.collect_metrics()
        assert _metric(fams, "kubeshare_serving_handoff_retries_total",
                       outcome="dropped") == 3
        assert _metric(fams, "kubeshare_serving_handoff_retries_total",
                       outcome="expired") >= 1

    def test_backoff_defers_redelivery_without_busy_spin(self):
        """A dropped delivery schedules the NEXT attempt exponentially
        later in router steps; the ticket eventually delivers and the
        ledger shows the retry."""
        from kubeshare_tpu.serving import Request
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        want = self._mono_streams(params, config)
        plan = FaultPlan(seed=9).drop_ticket(0)
        router = self._router(params, config, handoff_ttl_steps=50,
                              handoff_backoff_steps=2,
                              handoff_backoff_cap_steps=8)
        router.fault_clock = FaultClock(plan)
        router.warmup()
        for r in self._trace():
            router.submit(Request(**r))
        got = {k: v.tokens for k, v in router.run().items()}
        assert got == want
        assert router.handoff_retries["dropped"] == 1
        assert router.handoff_retries["expired"] == 0
        assert router.handoff_retries["delivered"] == len(self._trace())

    def test_ttl_constructor_validation(self):
        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        for kwargs in (dict(handoff_ttl_steps=0),
                       dict(handoff_backoff_steps=0),
                       dict(handoff_backoff_steps=4,
                            handoff_backoff_cap_steps=2)):
            with pytest.raises(ValueError):
                self._router(params, config, **kwargs)


class _OneShotServer:
    """A tokend stand-in: answers each connection's first line with a
    canned reply — enough to exercise the client's retry loop."""

    def __init__(self, replies):
        self._sock = socket.socket()
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(8)
        self.port = self._sock.getsockname()[1]
        self._thread = threading.Thread(
            target=self._serve, args=(list(replies),), daemon=True)
        self._thread.start()

    def _serve(self, replies):
        while replies:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            reply = replies.pop(0)
            f = conn.makefile("rw", newline="\n")
            if f.readline() and reply is not None:
                f.write(reply)
                f.flush()
            conn.close()

    def close(self):
        self._sock.close()


class TestTokendRetry:
    def test_transient_refusal_recovers_with_metered_retry(self):
        from kubeshare_tpu.isolation.client import TokenClient
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        srv = _OneShotServer(["PONG\n"])
        try:
            client = TokenClient("127.0.0.1", srv.port, "ns/pod-a",
                                 max_retries=3)
            client.fault_clock = FaultClock(
                FaultPlan(seed=3).refuse_tokend(0))
            assert client._round_trip("PING ns/pod-a\n") == "PONG"
            assert client.retry_counts == {
                "retried": 1, "recovered": 1, "exhausted": 0}
            fams = client.collect_metrics()
            assert _metric(fams, "kubeshare_tokend_retries_total",
                           outcome="recovered") == 1
            # the refusal burned virtual, not wall, time
            assert client.fault_clock.now() > 0
        finally:
            srv.close()

    def test_permanent_failure_still_raises_after_bounded_attempts(self):
        from kubeshare_tpu.isolation.client import TokenClient

        client = TokenClient("127.0.0.1", 1, "ns/pod-a", max_retries=2)
        client.BACKOFF_BASE_S = 0.001  # keep the test fast
        with pytest.raises(ConnectionError, match="unreachable after 3"):
            client._round_trip("PING ns/pod-a\n")
        assert client.retry_counts["exhausted"] == 1
        assert client.retry_counts["retried"] == 2

    def test_backoff_is_bounded_exponential_with_deterministic_jitter(self):
        from kubeshare_tpu.isolation.client import TokenClient

        a = TokenClient("127.0.0.1", 1, "ns/pod-a")
        b = TokenClient("127.0.0.1", 1, "ns/pod-b")
        sched_a = [a._backoff_s(k) for k in range(8)]
        # deterministic: same pod, same schedule
        assert sched_a == [a._backoff_s(k) for k in range(8)]
        # jittered: different pods don't sync their storms
        assert sched_a != [b._backoff_s(k) for k in range(8)]
        # bounded: jitter is +/-25% around an exponential, capped
        for k, s in enumerate(sched_a):
            base = min(a.BACKOFF_CAP_S, a.BACKOFF_BASE_S * (2 ** k))
            assert 0.75 * base <= s <= 1.25 * base
        assert sched_a[-1] <= 1.25 * a.BACKOFF_CAP_S

    def test_max_retries_validation(self):
        from kubeshare_tpu.isolation.client import TokenClient

        with pytest.raises(ValueError):
            TokenClient("127.0.0.1", 1, "ns/pod-a", max_retries=-1)


class TestFabricChaos:
    """The fabric's chaos seams: seeded frame drop / duplicate /
    reorder / corruption across the cluster KV fabric, and rotten disk
    sectors under the DISK tier — every fault is absorbed by the
    at-least-once redelivery contract (or the crc) and the streams stay
    BIT-EXACT with the fault-free arm."""

    def test_fabric_builders_validate_and_chain(self):
        from kubeshare_tpu.serving.chaos import FaultPlan

        plan = (FaultPlan(seed=9).drop_fabric(0).duplicate_fabric(2)
                .reorder_fabric(4).corrupt_fabric(6)
                .corrupt_disk_read(1))
        assert plan.fabric_drops == {0}
        assert plan.fabric_duplicates == {2}
        assert plan.fabric_reorders == {4}
        assert plan.fabric_corruptions == {6}
        assert plan.disk_corruptions == {1}
        for bad in (lambda p: p.drop_fabric(-1),
                    lambda p: p.duplicate_fabric(-1),
                    lambda p: p.reorder_fabric(-1),
                    lambda p: p.corrupt_fabric(-1),
                    lambda p: p.corrupt_disk_read(-1)):
            with pytest.raises(ValueError):
                bad(FaultPlan())

    def test_fabric_transmit_faults_are_seeded_and_deterministic(self):
        """Replay determinism at the seam: the same plan mutates the
        same frame the same way; a different seed flips a different
        bit."""
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        frame = bytes(range(64)) * 3

        def run(seed):
            clock = FaultClock(FaultPlan(seed=seed).corrupt_fabric(0))
            return clock.on_fabric_transmit(frame)

        a, b, c = run(3), run(3), run(4)
        assert a == b and a != c
        assert len(a) == 1 and len(a[0][0]) == len(frame)
        clock = FaultClock(FaultPlan(seed=3).drop_fabric(0)
                           .duplicate_fabric(1).reorder_fabric(2))
        assert clock.on_fabric_transmit(frame) == []
        assert clock.on_fabric_transmit(frame) == [(frame, False),
                                                   (frame, False)]
        assert clock.on_fabric_transmit(frame) == [(frame, True)]
        assert clock.on_fabric_transmit(frame) == [(frame, False)]
        kinds = [e[0] for e in clock.events]
        assert kinds == ["drop_fabric", "duplicate_fabric",
                         "reorder_fabric"]

    def test_fleet_drain_over_faulty_fabric_bit_exact(self):
        """Drain inheritance over a fabric losing, duplicating,
        reordering AND corrupting frames: redelivery recovers every
        chain, the survivor still inherits the retiree's prefix, the
        streams equal the fault-free fleet's, and the send-side
        counters reconcile (delivered + expired == sent, nothing in
        flight)."""
        from kubeshare_tpu.serving import Request
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan
        from kubeshare_tpu.serving.fabric import LoopbackTransport

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)

        def run(clock):
            fleet = _fleet(params, config, shared_tier_bytes=1 << 20,
                           fault_clock=clock,
                           fabric=LoopbackTransport(),
                           fabric_ttl_ticks=12)
            fleet.warmup()
            rng = np.random.default_rng(11)
            shared = rng.integers(0, 64, 16)

            def req(rid):
                return Request(rid, np.concatenate(
                    [shared, rng.integers(0, 64, 4)]), 4)

            streams = {}
            fleet.submit(req("seed"))
            streams.update(
                {r: o.tokens for r, o in fleet.run().items()})
            owner = fleet.owner_of("seed")
            fleet.drain(owner)
            fleet.run()
            fleet.submit(req("heir"))
            streams.update(
                {r: o.tokens for r, o in fleet.run().items()})
            return fleet, streams

        plan = FaultPlan(seed=21)
        # rough the early frames up: ordinals count EVERY transmit
        # (data, acks, redeliveries), so this hits a mix of both
        for n in (0, 5):
            plan.drop_fabric(n)
        plan.corrupt_fabric(2).duplicate_fabric(3).reorder_fabric(7)
        clock = FaultClock(plan)
        chaotic, got = run(clock)
        _, want = run(None)
        assert got == want  # bit-exact with the fault-free arm
        faults = {e[0] for e in clock.events}
        assert "drop_fabric" in faults and "corrupt_fabric" in faults
        eps = list(chaotic._endpoints.values()) + [chaotic._fleet_ep]
        assert all(ep.inflight == 0 for ep in eps)
        sent = sum(ep.messages.get(("chain", "sent"), 0) for ep in eps)
        delivered = sum(ep.messages.get(("chain", "delivered"), 0)
                        for ep in eps)
        expired = sum(ep.messages.get(("chain", "expired"), 0)
                      for ep in eps)
        assert sent > 0 and delivered + expired == sent
        assert sum(ep.redeliveries for ep in eps) > 0
        fams = chaotic.collect_metrics()
        assert _metric(fams,
                       "kubeshare_serving_fabric_redeliveries_total") > 0
        # the survivor still inherited the retiree's prefix
        assert chaotic.fabric_adopted_tokens > 0

    def test_disagg_tickets_over_faulty_fabric_bit_exact(self):
        """Handoff tickets through a lossy fabric: a dropped ticket
        frame redelivers under backoff, a dropped ACK dedups on the
        decode side, and the split-pool streams still equal the
        monolithic engine's token for token."""
        from kubeshare_tpu.serving import (DisaggRouter, EngineConfig,
                                           Request, ServingEngine)
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan
        from kubeshare_tpu.serving.fabric import LoopbackTransport

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)

        def reqs():
            return [Request(
                f"r{i}", np.arange(3 + i * 2) % 60, 8,
                temperature=(0.0 if i % 2 else 0.7),
                rng=(None if i % 2 else jax.random.PRNGKey(100 + i)))
                for i in range(5)]

        mono = ServingEngine(params, config, EngineConfig(
            num_slots=3, block_size=4, num_blocks=41,
            max_request_len=48, prefill_chunk=8, mixed=False))
        for r in reqs():
            mono.submit(r)
        want = {rid: res.tokens for rid, res in mono.run().items()}

        plan = (FaultPlan(seed=31).drop_fabric(0).drop_fabric(3)
                .duplicate_fabric(5).corrupt_fabric(7))
        clock = FaultClock(plan)
        fabric = LoopbackTransport()
        fabric.fault_clock = clock
        router = DisaggRouter(
            params, config,
            EngineConfig(num_slots=2, block_size=4, num_blocks=17,
                         max_request_len=48, prefill_chunk=8,
                         mixed=False),
            EngineConfig(num_slots=3, block_size=4, num_blocks=25,
                         max_request_len=48, prefill_chunk=8,
                         mixed=False),
            fabric=fabric, fabric_ttl_ticks=12)
        for r in reqs():
            router.submit(r)
        got = {rid: res.tokens for rid, res in router.run().items()}
        assert got == want
        assert clock.events  # the plan actually fired
        assert router._fabric_inflight == {}
        assert router._fabric_arrivals == []
        pf, dc = router._fabric_pf, router._fabric_dc
        assert pf.inflight == 0
        assert (pf.messages.get(("ticket", "delivered"), 0)
                + pf.messages.get(("ticket", "expired"), 0)
                == pf.messages[("ticket", "sent")])
        assert pf.redeliveries + dc.redeliveries > 0

    def test_disk_rot_is_a_loud_miss_not_wrong_tokens(self):
        """Rot EVERY disk sector read: each staged promotion detects
        the flip (block crc), drops the node's subtree, and the request
        re-prefills cold — the stream equals the dense reference, and
        the corruption is counted on the metrics plane."""
        from kubeshare_tpu.models.decoding import greedy_decode
        from kubeshare_tpu.serving import (EngineConfig, Request,
                                           ServingEngine,
                                           wire_block_bytes)
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)
        full_wire = wire_block_bytes(4, config.n_layers, config.kv_heads,
                                     4, config.head_dim, 4)
        engine = ServingEngine(params, config, EngineConfig(
            num_slots=1, block_size=4, num_blocks=13,
            max_request_len=32, prefill_chunk=8,
            host_tier_bytes=3 * full_wire, disk_tier_bytes=1 << 20))
        plan = FaultPlan(seed=23)
        for n in range(200):
            plan.corrupt_disk_read(n)
        engine.disk_tier.fault_clock = FaultClock(plan)
        rng = np.random.default_rng(11)
        shared = rng.integers(0, 64, 13)
        for rid, prompt in (("r0", shared),
                            ("f1", rng.integers(0, 64, 29)),
                            ("f2", rng.integers(0, 64, 29))):
            engine.submit(Request(rid, prompt, 3))
            engine.run()
            engine.pop_finished()
        assert engine.disk_tier.stored_blocks > 0
        hit = np.concatenate([shared, rng.integers(0, 64, 4)])
        engine.submit(Request("hit", hit, 3))
        out = engine.run()
        ref = np.asarray(greedy_decode(
            params, config, jnp.asarray(hit, jnp.int32)[None], 3))[0]
        assert out["hit"].tokens == list(ref)
        assert engine.disk_tier.corrupt_reads > 0
        fams = engine.collect_metrics()
        assert _metric(fams,
                       "kubeshare_serving_disk_tier_blocks_total",
                       event="corrupt_read") > 0
