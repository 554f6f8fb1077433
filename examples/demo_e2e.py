"""End-to-end demo: the full kubeshare-tpu stack in one process.

Spins up the scheduler (in-memory cluster), submits two fractional MNIST
pods, lets configd write the chip share tables, starts the REAL native
token runtime (tpushare-tokend + per-pod tpushare-pmgr), and runs both
pods' training loops token-gated — then tears one pod down and shows
reclamation.  Run: JAX_PLATFORMS=cpu python -m examples.demo_e2e
"""

from __future__ import annotations

import os
import socket
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp  # noqa: E402

from kubeshare_tpu import constants  # noqa: E402
from kubeshare_tpu.cell import load_config  # noqa: E402
from kubeshare_tpu.cell.allocator import ChipInfo  # noqa: E402
from kubeshare_tpu.cluster.api import FakeClock, Node, Pod, PodPhase  # noqa: E402
from kubeshare_tpu.configd import ConfigDaemon  # noqa: E402
from kubeshare_tpu.cluster.fake import FakeCluster  # noqa: E402
from kubeshare_tpu.isolation import ExecutionGuard, TokenClient  # noqa: E402
from kubeshare_tpu.utils.net import wait_listening  # noqa: E402
from kubeshare_tpu.models import mnist_apply, mnist_init  # noqa: E402
from kubeshare_tpu.parallel.train import cross_entropy_loss, make_train_step  # noqa: E402
from kubeshare_tpu.runtime import ChipSupervisor  # noqa: E402
from kubeshare_tpu.scheduler import KubeShareScheduler, SchedulerEngine  # noqa: E402

TOPOLOGY = """
cellTypes:
  DEMO-NODE:
    childCellType: "TPU-v5e"
    childCellNumber: 1
    childCellPriority: 80
    isNodeLevel: true
cells:
- cellType: DEMO-NODE
  cellId: demo-node
"""


def banner(text: str) -> None:
    print(f"\n=== {text} ===", flush=True)


def main() -> None:
    chip = "demo-node-tpu-0"
    workdir = tempfile.mkdtemp(prefix="tpushare-demo-")

    banner("1. control plane: scheduler + inventory")
    cluster = FakeCluster()
    cluster.add_node(Node("demo-node", {constants.NODE_LABEL_FILTER: "true"}))
    plugin = KubeShareScheduler(
        load_config(text=TOPOLOGY), cluster,
        lambda n: [ChipInfo(chip, 16 << 30, "TPU-v5e", 0)],
        clock=FakeClock(0.0),
    )
    engine = SchedulerEngine(plugin, cluster, plugin.clock)
    print(f"registered node demo-node with 1 x TPU-v5e ({chip})")

    banner("2. submit two fractional pods (request 0.5 / limit 1.0)")
    for name in ("mnist-a", "mnist-b"):
        cluster.create_pod(Pod(
            name=name,
            labels={constants.POD_GPU_REQUEST: "0.5",
                    constants.POD_GPU_LIMIT: "1.0",
                    constants.POD_GPU_MEMORY: str(4 << 30)},
            scheduler_name=constants.SCHEDULER_NAME,
        ))
    for result in engine.run_until_idle():
        pod = cluster.get_pod("default", result.pod_key.split("/")[1])
        print(f"  {result.pod_key}: {result.result} on {result.node} "
              f"chip={pod.annotations[constants.POD_GPU_UUID]} "
              f"port={pod.annotations[constants.POD_MANAGER_PORT]}")
        cluster.set_pod_phase(pod.namespace, pod.name, PodPhase.RUNNING)

    banner("3. node daemon: configd writes the chip share table")
    config_dir = os.path.join(workdir, "config")
    port_dir = os.path.join(workdir, "ports")
    daemon = ConfigDaemon("demo-node", cluster=cluster,
                          config_dir=config_dir, port_dir=port_dir)
    daemon.sync()
    print(open(os.path.join(config_dir, chip)).read().strip())

    banner("4. native runtime: tokend + per-pod pmgr brokers")
    s = socket.socket(); s.bind(("127.0.0.1", 0))
    tokend_port = s.getsockname()[1]; s.close()
    with ChipSupervisor(chip, config_dir=config_dir, port_dir=port_dir,
                        tokend_port=tokend_port, poll_interval=0.2) as sup:
        wait_listening(tokend_port)
        for name in ("mnist-a", "mnist-b"):
            pod = cluster.get_pod("default", name)
            wait_listening(int(pod.annotations[constants.POD_MANAGER_PORT]))
        print(f"tokend on :{tokend_port}, pod managers: "
              f"{sorted(sup.pod_managers)}")

        banner("5. token-gated training (both pods share the chip)")
        for name in ("mnist-a", "mnist-b"):
            pod = cluster.get_pod("default", name)
            client = TokenClient(
                "127.0.0.1", int(pod.annotations[constants.POD_MANAGER_PORT]),
                "stamped-by-pmgr")
            guard = ExecutionGuard(client=client, from_env=False)
            init_state, train_step = make_train_step(
                mnist_apply, loss_fn=cross_entropy_loss)
            state = init_state(mnist_init(jax.random.PRNGKey(0)))
            images = jnp.zeros((8, 28, 28, 1))
            labels = jnp.zeros((8,), jnp.int32)
            for _ in range(3):
                guard.acquire()
                t0 = time.monotonic()
                state, loss = train_step(state, images, labels)
                jax.block_until_ready(loss)
                guard.charge((time.monotonic() - t0) * 1e3)
            guard.finish()
            print(f"  {name}: 3 steps, loss {float(loss):.3f}, "
                  f"tokens {guard.tokens_acquired}")

        stat_client = TokenClient("127.0.0.1", tokend_port, "probe")
        print("tokend accounting:", stat_client.stat())
        stat_client.close()

        banner("6. teardown: delete mnist-a, watch reclamation")
        cluster.delete_pod("default", "mnist-a")
        daemon.sync()
        time.sleep(1.0)
        leaf = plugin.allocator.leaf_cells[chip]
        print(f"chip availability back to {leaf.available} "
              f"(free HBM {leaf.free_memory >> 30} GiB); "
              f"pod managers now: {sorted(sup.pod_managers)}")
    print("\ndemo complete")


if __name__ == "__main__":
    main()
