"""Stands the system under test up the way a node does: native token
runtime, scheduler placement, configd's share table, the per-chip
supervisor, one guard per pod, pod A's ``ServingEngine`` and, where the mix
asks for one, pod B's gated matmul chain on a thread.

The shape is ``chip_smoke.py``'s control-plane, guard and co-tenant phases;
from the program come only the system itself and its counters.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import threading
import time
from typing import Callable, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE_BINARIES = ("tpushare-tokend", "tpushare-pmgr")


def ensure_native() -> float:
    """Build the native runtime against the tracked header, only when
    ``native/build`` lacks the binaries.  Returns the seconds it took."""
    from kubeshare_tpu.runtime import find_binary

    build = os.path.join(REPO, "native", "build")
    if all(os.path.isfile(os.path.join(build, b)) for b in NATIVE_BINARIES):
        return 0.0
    started = time.monotonic()
    native = os.path.join(REPO, "native")
    proc = subprocess.run(
        ["make", "-C", native,
         f"PJRT_INC={os.path.join(native, 'third_party', 'xla')}"],
        capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"native build failed (rc {proc.returncode}):\n"
                           f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    for name in NATIVE_BINARIES:
        if find_binary(name) != os.path.join(build, name):
            raise RuntimeError(f"{name} was not built into {build}")
    return time.monotonic() - started


class ControlPlane:
    """Scheduler -> configd -> supervisor on the discovered chip."""

    def __init__(self, inventory: List, pods: List[Dict], workdir: str):
        from kubeshare_tpu import constants
        from kubeshare_tpu.cell.topology import generate_tpu_topology_config
        from kubeshare_tpu.cluster.api import FakeClock, Node, Pod, PodPhase
        from kubeshare_tpu.cluster.fake import FakeCluster
        from kubeshare_tpu.configd import ConfigDaemon
        from kubeshare_tpu.runtime import ChipSupervisor
        from kubeshare_tpu.scheduler import KubeShareScheduler, SchedulerEngine
        from kubeshare_tpu.utils.net import free_port, wait_listening

        self._closers: List[Callable[[], None]] = []
        chip = inventory[0]
        node = socket.gethostname()
        cluster = FakeCluster()
        cluster.add_node(Node(node, {constants.NODE_LABEL_FILTER: "true"}))
        plugin = KubeShareScheduler(
            generate_tpu_topology_config([(node, chip.model, len(inventory))]),
            cluster, lambda n: list(inventory) if n == node else [],
            clock=FakeClock(0.0))
        scheduler = SchedulerEngine(plugin, cluster, plugin.clock)
        for pod in pods:
            cluster.create_pod(Pod(
                name=pod["name"],
                labels={
                    constants.POD_GPU_REQUEST: str(pod["gpu_request"]),
                    constants.POD_GPU_LIMIT: str(pod["gpu_limit"]),
                    constants.POD_GPU_MEMORY:
                        str(int(chip.memory * pod["gpu_mem"]))},
                scheduler_name=constants.SCHEDULER_NAME))
        self.pods: Dict[str, Dict] = {}
        for result in scheduler.run_until_idle():
            pod = cluster.get_pod(*result.pod_key.split("/"))
            if pod is None or not pod.is_bound():
                raise RuntimeError(f"{result.pod_key} not placed: {result}")
            if pod.annotations[constants.POD_GPU_UUID] != chip.uuid:
                raise RuntimeError(f"{result.pod_key} placed off the chip")
            cluster.set_pod_phase(pod.namespace, pod.name, PodPhase.RUNNING)
            self.pods[pod.name] = {
                "key": pod.get_env(constants.ENV_POD_NAME),
                "port": int(pod.get_env(constants.ENV_POD_MANAGER_PORT)),
                "mem_fraction": float(pod.get_env(constants.ENV_MEM_FRACTION)),
            }
        wanted = sorted(p["name"] for p in pods)
        if sorted(self.pods) != wanted:
            raise RuntimeError(f"placed {sorted(self.pods)}, wanted {wanted}")
        config_dir = os.path.join(workdir, "config")
        port_dir = os.path.join(workdir, "podmanagerport")
        ConfigDaemon(node, cluster=cluster, config_dir=config_dir,
                     port_dir=port_dir).sync()
        with open(os.path.join(config_dir, chip.uuid)) as f:
            self.share_table = [r for r in f.read().split("\n")[1:] if r]
        if len(self.share_table) != len(pods):
            raise RuntimeError(f"share table rows: {self.share_table}")
        self.tokend_port = free_port()
        supervisor = ChipSupervisor(
            chip.uuid, config_dir=config_dir, port_dir=port_dir,
            tokend_port=self.tokend_port, poll_interval=0.2)
        supervisor.start()
        self._closers.append(supervisor.stop)
        wait_listening(self.tokend_port)
        for pod in self.pods.values():
            wait_listening(pod["port"])

    def guard(self, name: str):
        """A guard under one pod's identity, through its own pmgr port.
        An unreachable broker is a failure, never "running ungated"."""
        from kubeshare_tpu.isolation import ExecutionGuard, TokenClient

        pod = self.pods[name]
        client = TokenClient("127.0.0.1", pod["port"], pod["key"])
        client.ping()
        self._closers.append(client.close)
        guard = ExecutionGuard(client=client, from_env=False)
        if not guard.gated:
            raise RuntimeError(f"guard for {name} is not gated")
        return guard

    def stat(self) -> Dict[str, Dict]:
        """tokend's STAT by pod NAME (not the scheduler-injected key)."""
        from kubeshare_tpu.isolation import TokenClient

        client = TokenClient("127.0.0.1", self.tokend_port, "chipbench/stat")
        try:
            by_key = json.loads(client.stat())["pods"]
        finally:
            client.close()
        return {name: by_key[pod["key"]] for name, pod in self.pods.items()
                if pod["key"] in by_key}

    def close(self) -> None:
        while self._closers:
            self._closers.pop()()


class GuardProxy:
    """The guard the engine is handed: forwards everything, and times and
    names the wait in ``acquire`` (nothing in the program does)."""

    def __init__(self, guard, annotate) -> None:
        self._guard = guard
        self._annotate = annotate
        self.acquire_calls = 0
        self.acquire_wait_s = 0.0

    @property
    def gated(self) -> bool:
        return self._guard.gated

    @property
    def tokens_acquired(self) -> int:
        return self._guard.tokens_acquired

    @property
    def total_gated_ms(self) -> float:
        return self._guard.total_gated_ms

    def acquire(self) -> float:
        start = time.monotonic()
        with self._annotate("chipbench.guard.acquire"):
            quota = self._guard.acquire()
        self.acquire_wait_s += time.monotonic() - start
        self.acquire_calls += 1
        return quota

    def charge(self, elapsed_ms: float) -> None:
        self._guard.charge(elapsed_ms)

    def finish(self) -> None:
        self._guard.finish()


class Cotenant:
    """Pod B: a gated chain of bf16 matrix products on a thread, with its
    own client and guard, for as long as it is left running."""

    def __init__(self, guard, spec: Dict) -> None:
        import jax
        import jax.numpy as jnp

        n, chain = int(spec["matmul_n"]), int(spec["chain"])
        self._guard = guard
        self._x = jnp.ones((n, n), jnp.dtype(spec["dtype"]))

        @jax.jit
        def step(a):
            for _ in range(chain):
                a = (a @ a) * (1.0 / n)
            return a

        self._step = step
        jax.block_until_ready(step(self._x))  # compile before it is gated
        self.done_at: List[float] = []  # monotonic time of each finished step
        self.error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, name="pod-b",
                                        daemon=True)

    def _loop(self) -> None:
        import jax

        try:
            while not self._stop.is_set():
                self._guard.acquire()
                t0 = time.monotonic()
                jax.block_until_ready(self._step(self._x))
                t1 = time.monotonic()
                self._guard.charge((t1 - t0) * 1e3)
                self.done_at.append(t1)
        except BaseException as e:  # re-raised by stop() on the main thread
            self.error = e
        finally:
            self._guard.finish()

    def start(self) -> None:
        self._thread.start()

    def steps_between(self, t0: float, t1: float) -> int:
        return sum(1 for t in list(self.done_at) if t0 <= t < t1)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=60)
        if self._thread.is_alive():
            raise RuntimeError("pod B's loop did not stop")
        if self.error is not None:
            raise RuntimeError(f"pod B's loop failed: {self.error!r}")


def pool_bytes(pool) -> int:
    """The bytes of the device arrays an engine's pool holds."""
    import jax

    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(vars(pool))
               if isinstance(x, jax.Array))


def build_engine(cfg: Dict, params, guard, counts):
    """Pod A's engine: the configuration file fixes the model, the memory
    geometry and ``prefill_chunk``; every other scheduling field stays at
    the program's default.  ``counts`` is the configuration's byte-count
    module: its ``kv_bytes_per_row`` sizes the pool, and is held against
    what the program then allocates, so that a count that is wrong can
    neither mis-size a pool nor flatter a roofline share unseen."""
    import jax.numpy as jnp

    from kubeshare_tpu.models.transformer import TransformerConfig
    from kubeshare_tpu.serving import EngineConfig, ServingEngine

    tc = dict(cfg["transformer_config"])
    tc["dtype"] = jnp.dtype(tc["dtype"])
    config = TransformerConfig(**tc)
    e = cfg["engine"]
    per_block = counts.kv_bytes_per_row(cfg["transformer_config"]) \
        * e["block_size"]
    ec = EngineConfig(
        num_slots=e["num_slots"], block_size=e["block_size"],
        num_blocks=e["pool_bytes"] // per_block + 1,  # + scratch block 0
        max_request_len=e["max_request_len"],
        prefill_chunk=e["prefill_chunk"])
    engine = ServingEngine(params, config, ec, guard=guard)
    counted, held = ec.num_blocks * per_block, pool_bytes(engine.pool)
    if counted != held:
        raise RuntimeError(
            f"{counts.__name__}.kv_bytes_per_row says {per_block} B a block "
            f"of {e['block_size']} rows, {counted} B for {ec.num_blocks} "
            f"blocks; the program's pool holds {held} B")
    return engine
