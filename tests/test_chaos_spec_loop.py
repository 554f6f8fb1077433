"""Fault-injection suite: verify-in-loop launches under seeded chaos.

The rules are those ``tests/test_chaos.py`` states (a real serving stack, a
seeded ``FaultPlan`` through the chaos seams, streams BIT-EXACT with the
fault-free run); these two cases are the suite's longest, and a tier-1
worker holds a file for its whole length (``--dist loadfile``)."""

import jax
import numpy as np
import pytest

from kubeshare_tpu.models.transformer import transformer_init

from chaos_helpers import _PinFirst, _fleet
from serving_helpers import _small_config

pytestmark = [pytest.mark.serving, pytest.mark.chaos]


class TestSpecLoopChaos:
    """Verify-in-loop launches under chaos: a kill at the loop dispatch
    boundary must drain the in-flight K-unit token ring (and the
    admission ring's staged lanes) before orphan re-admission, and the
    fleet watchdog must budget a K-unit launch as K dispatches' work."""

    def _spec_trace(self):
        """Repetitive prompts so the n-gram drafter proposes on every
        lane — the decode phase goes all-drafted and the engine plans
        verify-in-loop launches; greedy and sampled lanes mixed."""
        from kubeshare_tpu.serving import Request

        rng = np.random.default_rng(29)
        out = []
        for i in range(6):
            pat = rng.integers(0, 64, 4)
            prompt = np.concatenate([np.tile(pat, 3),
                                     rng.integers(0, 64, 2)])
            key = (jax.random.PRNGKey(80 + i) if i % 3 == 2 else None)
            out.append(Request(
                f"r{i}", prompt, 8,
                temperature=(0.8 if key is not None else 0.0), rng=key))
        return out

    def test_kill_at_loop_boundary_drains_ring_bit_exact(self):
        """Kill the replica exactly at a loop dispatch boundary — a
        K-unit verify-in-loop launch completed on the wire but its
        token ring never reached host state.  Recovery must drain it
        first (emissions, retirements, ring activations), then re-admit
        the orphans; every stream matches the fault-free run token for
        token, greedy and sampled."""
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)

        def build(fault_clock=None):
            fleet = _fleet(params, config, routing=_PinFirst(),
                           num_blocks=41, speculative=True,
                           steps_per_launch=4, admission_ring=2,
                           top_k=10, top_p=0.95, fault_clock=fault_clock)
            fleet.warmup()
            for r in self._spec_trace():
                fleet.submit(r)
            return fleet

        ref = build()
        want = {k: v.tokens for k, v in ref.run().items()}
        assert ref._handle("r0").engine.spec_loop_launches > 0, \
            "trace never engaged the spec loop"

        plan = FaultPlan(seed=31)
        clock = FaultClock(plan)
        fleet = build(clock)
        eng = fleet._handle("r0").engine
        while not (eng._inflight is not None
                   and eng._inflight[0] == "spec_loop"):
            assert fleet.step(), \
                "trace drained before a spec-loop launch was in flight"
        plan.kill("r0", at_step=clock._steps.get("r0", 0))
        got = {k: v.tokens for k, v in fleet.run().items()}
        assert got == want
        assert fleet.replica_failures == {"liveness": 1}
        # the in-flight launch was drained into host state before the
        # orphan walk: nothing left in flight, no staged lane stranded
        assert eng._inflight is None
        assert eng._ring_staged == []
        assert fleet.orphans_readmitted > 0

    def test_watchdog_budget_covers_k_unit_launches(self):
        """A healthy K-unit verify-in-loop launch legitimately takes K
        dispatches' worth of time in one step; the watchdog must budget
        it by the launch envelope instead of flagging it hung.  The
        injected delay is OVER the per-dispatch budget (a flat budget
        would kill the replica) but inside K times it."""
        from kubeshare_tpu.serving.chaos import FaultClock, FaultPlan

        config = _small_config()
        params = transformer_init(jax.random.PRNGKey(0), config)

        def build(fault_clock, **kw):
            return _fleet(params, config, routing=_PinFirst(),
                          num_blocks=41, speculative=True,
                          steps_per_launch=4, top_k=10, top_p=0.95,
                          fault_clock=fault_clock, **kw)

        # record pass: which of r0's dispatch ordinals are spec-loop
        # launches (the launch is the step's last dispatch)
        clock = FaultClock(FaultPlan(seed=37))
        fleet = build(clock)
        fleet.warmup()
        results = {}
        for r in self._spec_trace():
            results[r.rid] = fleet.submit(r)
        eng = fleet._handle("r0").engine
        loop_ordinals = []
        while fleet.step():
            if eng._inflight is not None \
                    and eng._inflight[0] == "spec_loop":
                loop_ordinals.append(clock._dispatches["r0"] - 1)
        want = {rid: res.tokens for rid, res in results.items()}
        assert loop_ordinals, "trace never engaged the spec loop"

        budget, delay = 0.05, 0.12
        assert delay > budget          # flat budget would trip...
        assert delay < 4 * budget      # ...the launch envelope must not
        plan = FaultPlan(seed=37)
        for n in loop_ordinals:
            plan.slow_dispatch("r0", n, delay)
        clock2 = FaultClock(plan)
        fleet2 = build(clock2, watchdog_budget_s=budget, watchdog_grace=1)
        fleet2.warmup()
        for r in self._spec_trace():
            fleet2.submit(r)
        got = {k: v.tokens for k, v in fleet2.run().items()}
        assert got == want
        assert fleet2.replica_failures == {}
        assert fleet2._handle("r0").state == "active"
        landed = sum(1 for e in clock2.events if e[0] == "slow_dispatch")
        assert landed == len(loop_ordinals)
