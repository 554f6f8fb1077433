"""Execution gating + HBM caps for JAX workloads.

The TPU-native enforcement points (SURVEY §7.2):

- **Compute share**: XLA dispatches whole compiled programs, so the guard
  brackets each step — acquire a token from the chip's tokend, run the
  jitted step, ``block_until_ready``, release with measured wall time.
  This is the in-process equivalent of the PJRT interposer's Execute hook
  (and what Gemini did per kernel burst).
- **HBM cap**, three reinforcing levels (strongest first):
  1. placement admission — the scheduler only co-locates pods whose HBM
     requests fit the chip (the hard guarantee, like k8s memory requests);
  2. broker accounting — the PJRT interposer charges every host->device
     upload AND every executable output buffer against the pod's cap via
     the MEM protocol (credited on buffer destroy); over-cap allocations
     are hard-denied by default (fabricated RESOURCE_EXHAUSTED), or
     log-only with TPUSHARE_MEM_ENFORCE=soft;
  3. client flags — ``apply_hbm_cap`` translates the scheduler-injected
     TPUSHARE_MEM_FRACTION into XLA client allocator flags for in-process
     workloads; the LD_PRELOAD shim's constructor does the same for
     preload-only pods and additionally injects memory_fraction /
     preallocate create options at PJRT_Client_Create (fail-open where
     the plugin rejects them).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Optional, Tuple, TypeVar

from .. import constants
from ..utils.logger import get_logger
from ..utils.profiling import span
from .client import TokenClient, connect_from_env

F = TypeVar("F", bound=Callable)


def apply_hbm_cap(environ: Optional[dict] = None) -> Optional[float]:
    """Install the pod's HBM cap into the XLA client config.  MUST run
    before ``import jax`` triggers backend init.  Returns the fraction
    applied, or None when uncapped."""
    env = environ if environ is not None else os.environ
    fraction_raw = env.get(constants.ENV_MEM_FRACTION)
    if not fraction_raw:
        return None
    try:
        fraction = float(fraction_raw)
    except ValueError:
        return None
    if not 0.0 < fraction <= 1.0:
        return None
    # JAX reads these at backend init: cap the client allocator to the pod's
    # share and keep preallocation off so co-tenants can start in any order.
    env.setdefault("XLA_PYTHON_CLIENT_MEM_FRACTION", f"{fraction:.4f}")
    env.setdefault("XLA_PYTHON_CLIENT_PREALLOCATE", "false")
    return fraction


class ExecutionGuard:
    """Token-gates callables that dispatch work to the shared chip.

    Degrades gracefully: with no broker configured (solo run, tests) the
    guard is a no-op passthrough, so the same training script runs managed
    and unmanaged.
    """

    def __init__(self, client: Optional[TokenClient] = None,
                 from_env: bool = True, idle_release_ms: float = 200.0) -> None:
        self.log = get_logger("tpushim")
        if client is None and from_env:
            try:
                client = connect_from_env()
            except ConnectionError as e:
                self.log.warning("token broker unreachable, running ungated: %s", e)
                client = None
        self.client = client
        self._estimate_ms = 1.0  # EMA of step wall time
        self._budget_ms = 0.0  # remaining quota on the held token
        self._held_used_ms = 0.0  # device time consumed on the held token
        self._held = False
        self._lock = threading.RLock()
        self._last_activity = 0.0
        self._idle_release_ms = idle_release_ms
        self._in_flight = False  # between acquire() and charge(): a step runs
        self._monitor: Optional[threading.Thread] = None
        # the pod's name on this guard's spans (kubeshare.guard.*)
        self._pod = getattr(client, "pod_name", "")
        self._gated_span: Optional[span] = None  # acquire() .. charge()
        self.tokens_acquired = 0
        self.total_gated_ms = 0.0
        # every acquire(), and those of them that went to the token client
        # (the others found the held token's budget enough): calls and the
        # seconds they took, fed by the kubeshare.guard.acquire span
        self.acquire_calls = 0
        self.acquire_wait_s = 0.0
        self.broker_calls = 0
        self.broker_wait_s = 0.0

    @property
    def gated(self) -> bool:
        return self.client is not None

    def __call__(self, fn: F) -> F:
        if self.client is None:
            return fn

        def gated(*args: Any, **kwargs: Any) -> Any:
            self.acquire()
            start = time.monotonic()
            try:
                result = fn(*args, **kwargs)
                result = _block_until_ready(result)
            finally:
                elapsed_ms = (time.monotonic() - start) * 1e3
                self.charge(elapsed_ms)
            return result

        gated.__name__ = getattr(fn, "__name__", "gated")
        return gated  # type: ignore[return-value]

    def acquire(self) -> float:
        """Ensure a token with remaining budget is held.

        Tokens are *budgeted*: one grant covers many steps until its quota
        (ms of device time) is consumed — the Gemini token model (quota
        20-300ms per grant), without a broker round trip per step.  A
        monitor thread returns a held token after ``idle_release_ms`` of
        inactivity so an idle workload never starves co-tenants (relevant
        under the exclusive tokend mode).
        """
        if self.client is None:
            return 0.0
        with span("kubeshare.guard.acquire", pod=self._pod) as waited:
            quota, broker = self._acquire_locked()
            waited.set(broker=int(broker))
        self.acquire_calls += 1
        self.acquire_wait_s += waited.seconds
        if broker:
            self.broker_calls += 1
            self.broker_wait_s += waited.seconds
        # what tokend is charged for runs from here to charge()'s entry
        self._end_gated()  # left open by an acquire() never charged
        self._gated_span = span("kubeshare.guard.gated", pod=self._pod)
        self._gated_span.__enter__()
        return quota

    def _end_gated(self) -> None:
        if self._gated_span is not None:
            self._gated_span.__exit__(None, None, None)
            self._gated_span = None

    def _acquire_locked(self) -> Tuple[float, bool]:
        """(the held token's budget, whether the token client was asked)."""
        with self._lock:
            self._last_activity = time.monotonic()
            self._in_flight = True  # a step follows; idle monitor backs off
            # reuse the held token only when its remaining budget covers
            # the coming burst: running a full step on a sliver of
            # leftover budget overdraws the grant AND skips the broker's
            # re-arbitration — under exclusive co-tenancy that steals a
            # whole extra turn from a parked peer (an earlier round's
            # two-pod co-run lost ~25% of its aggregate before this check)
            if self._held and self._budget_ms >= 0.5 * self._estimate_ms:
                return self._budget_ms, False
            if self._held:
                self._release_held()
            quota = self.client.acquire(self._estimate_ms)
            self.tokens_acquired += 1
            self._held = True
            self._budget_ms = quota
            self._held_used_ms = 0.0
            self._ensure_monitor()
            return quota, True

    def charge(self, elapsed_ms: float) -> None:
        """Consume budget for one step; release the token when exhausted."""
        if self.client is None:
            return
        self._end_gated()
        with self._lock:
            self._last_activity = time.monotonic()
            self._in_flight = False
            self._estimate_ms = 0.8 * self._estimate_ms + 0.2 * elapsed_ms
            self.total_gated_ms += elapsed_ms
            self._budget_ms -= elapsed_ms
            self._held_used_ms += elapsed_ms
            # release at the step boundary once the budget cannot fund
            # another burst — holding a near-empty token through the
            # caller's input-pipeline wait idles the chip for exactly the
            # wait (the waiter is parked broker-side; work conservation
            # demands the handoff happen HERE, not at the idle monitor's
            # 200 ms horizon).  A budget still >= a step keeps amortizing
            # grants (many small steps per token, the Gemini quantum).
            if self._held and self._budget_ms < 0.5 * self._estimate_ms:
                self._release_held()

    # backwards-compatible single-step release
    def release(self, elapsed_ms: float) -> None:
        self.charge(elapsed_ms)

    def finish(self) -> None:
        """Return any held token (call when the workload goes idle)."""
        with self._lock:
            if self._held:
                self._release_held()

    def _release_held(self) -> None:
        assert self.client is not None
        self.client.release(self._held_used_ms)
        self._held = False
        self._budget_ms = 0.0
        self._held_used_ms = 0.0

    def _ensure_monitor(self) -> None:
        if self._monitor is not None or self._idle_release_ms <= 0:
            return

        def watch() -> None:
            while True:
                time.sleep(self._idle_release_ms / 1e3 / 4)
                with self._lock:
                    idle_ms = (time.monotonic() - self._last_activity) * 1e3
                    # never release mid-step: a long execution (first-step
                    # compile!) between acquire and charge is not idleness
                    if (self._held and not self._in_flight
                            and idle_ms >= self._idle_release_ms):
                        try:
                            self._release_held()
                        except ConnectionError:
                            # broker gone (teardown/restart); it reclaims the
                            # token via its own drop handling
                            self._held = False
                            self._budget_ms = 0.0

        self._monitor = threading.Thread(target=watch, daemon=True)
        self._monitor.start()

    def request_memory(self, delta_bytes: int) -> bool:
        if self.client is None:
            return True
        ok, used, cap = self.client.request_memory(delta_bytes)
        if not ok:
            self.log.warning(
                "HBM request denied: used %d + %d > cap %d", used, delta_bytes, cap
            )
        return ok


def _block_until_ready(result: Any) -> Any:
    """Wait for device completion so the measured time covers the real
    execution burst, not just async dispatch."""
    try:
        import jax

        return jax.block_until_ready(result)
    except ImportError:
        return result


def token_gated(fn: F) -> F:
    """Decorator: gate a step function with an env-configured guard."""
    return ExecutionGuard()(fn)
