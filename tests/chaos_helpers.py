"""What the fault-injection files (``tests/test_chaos*.py``) share beside
``serving_helpers._small_config``: a fleet at the suite's geometry, a metric
reader, the mixed trace and the router that pins the doomed replica."""

import jax
import numpy as np


def _fleet(params, config, *, replicas=2, num_blocks=21, **overrides):
    from kubeshare_tpu.serving import EngineConfig, ReplicaFleet

    ec_kwargs = dict(num_slots=3, block_size=4, num_blocks=num_blocks,
                     max_request_len=48, prefill_chunk=8)
    fleet_kwargs = dict(replicas=replicas)
    for k in ("routing", "tenants", "shared_tier_bytes", "clock",
              "fault_clock", "liveness_grace", "watchdog_budget_s",
              "watchdog_grace", "fabric", "fabric_ttl_ticks"):
        if k in overrides:
            fleet_kwargs[k] = overrides.pop(k)
    ec_kwargs.update(overrides)
    return ReplicaFleet(params, config, EngineConfig(**ec_kwargs),
                        **fleet_kwargs)


def _metric(families, name, **labels):
    total = 0.0
    for fam in families:
        for s in fam.samples:
            if s.name == name and all(
                    s.labels.get(k) == v for k, v in labels.items()):
                total += s.value
    return total


def _mixed_trace():
    """Greedy AND sampled lanes over a shared-prefix family — the
    rng construction order is part of the trace, so both arms must
    call this identically."""
    from kubeshare_tpu.serving import Request

    rng = np.random.default_rng(5)
    shared = rng.integers(0, 64, 12)
    out = []
    for i in range(8):
        if i % 2 == 0:
            prompt = np.concatenate([shared, rng.integers(0, 64, 4)])
        else:
            prompt = rng.integers(0, 64, 10)
        key = (jax.random.PRNGKey(70 + i) if i % 3 == 0 else None)
        out.append(Request(
            f"r{i}", prompt, 6,
            temperature=(0.8 if key is not None else 0.0), rng=key))
    return out


class _PinFirst:
    """Route everything to the first live candidate — keeps the doomed
    replica's ownership deterministic."""

    def route(self, fleet, request, candidates):
        return candidates[0], "least_loaded"
