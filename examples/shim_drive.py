#!/usr/bin/env python3
"""Drive a real JAX process under the LD_PRELOAD interposer.

This is the production isolation path (ref pkg/scheduler/pod.go:446-449
injected libgemhook.so.1 the same way): the scheduler sets
``LD_PRELOAD=libtpushim.so.1`` + ``POD_MANAGER_PORT``/``POD_NAME`` on a
fractional pod, and every PJRT Execute in the container is token-gated
with NO cooperation from the workload.  The in-repo tests exercise the
interposer against ``native/test/fake_pjrt_plugin.cc``; this script is
the real-runtime validation: a plain JAX training loop (which knows
nothing about tokens) runs under the shim against a live tokend, and the
tokend's STAT ledger shows the grants and device-time charges the shim
made on its behalf.

Usage:
    python examples/shim_drive.py            # real accelerator runtime
    python examples/shim_drive.py --cpu      # plumbing smoke (see below)

Prints a JSON verdict: {"gated": true, "grants": N, "charged_ms": ...}.

``--cpu`` exercises only the launch plumbing (tokend up, env wiring,
worker completes under LD_PRELOAD): jaxlib's CPU client is linked
in-process — there is no dlopen'd plugin for the interposer's dlsym hook
to rewrite — so ``gated`` is EXPECTED to be false there and the exit
code is 0.  The dlopen hook path itself is covered by the fake-plugin
tests (native/test/fake_pjrt_plugin.cc); gating a real workload needs
the real dlopen'd accelerator plugin (the default mode).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

WORKER = r"""
import json
import jax, jax.numpy as jnp

# a deliberately plain training loop: no kubeshare_tpu imports, no token
# client — if tokens show up at the broker they came from the interposer
def loss_fn(w, x, y):
    return jnp.mean((x @ w - y) ** 2)

step = jax.jit(lambda w, x, y: w - 0.01 * jax.grad(loss_fn)(w, x, y))
key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (256, 256))
x = jax.random.normal(key, (512, 256))
y = jax.random.normal(key, (512, 256))
for i in range(20):
    w = step(w, x, y)
w.block_until_ready()
dev = jax.devices()[0]
print("WORKER_DONE", json.dumps({
    "platform": dev.platform, "kind": dev.device_kind,
    "count": len(jax.devices())}))
"""


def drive(cpu: bool = False, timeout: float = 600.0) -> dict:
    """Run the worker under the shim against a live tokend; returns the
    verdict.  The caller stays off JAX: the child is the one process that
    opens the accelerator.  ``shim_log`` carries the interposer's stderr
    lines (header vs runtime PJRT versions among them)."""
    build = os.path.join(REPO, "native", "build")
    shim = os.path.join(build, "libtpushim.so.1")
    tokend = os.path.join(build, "tpushare-tokend")
    if not (os.path.isfile(shim) and os.path.isfile(tokend)):
        subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                       check=True, capture_output=True)

    from kubeshare_tpu.isolation import TokenClient
    from kubeshare_tpu.utils.net import free_port, wait_listening

    workdir = tempfile.mkdtemp(prefix="shim-drive-")
    uuid = "drive-chip-0"
    with open(os.path.join(workdir, uuid), "w") as f:
        f.write("1\ndrive/pod-a 1.0 0.5 0\n")
    port = free_port()
    tokend_proc = subprocess.Popen(
        [tokend, "-p", workdir, "-f", uuid, "-P", str(port),
         "-q", "300", "-m", "20", "-w", "10000"],
    )
    try:
        wait_listening(port, deadline_s=10)

        env = dict(os.environ)
        env.update({
            "LD_PRELOAD": shim,
            "POD_MANAGER_PORT": str(port),
            "POD_MANAGER_IP": "127.0.0.1",
            "POD_NAME": "drive/pod-a",
        })
        if cpu:
            env["JAX_PLATFORMS"] = "cpu"
        worker = subprocess.run(
            [sys.executable, "-u", "-c", WORKER], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=timeout,
        )
        shim_log = [ln for ln in worker.stderr.splitlines()
                    if ln.startswith("tpushim:")]
        done = [ln for ln in worker.stdout.splitlines()
                if ln.startswith("WORKER_DONE ")]
        if worker.returncode != 0 or not done:
            return {
                "gated": False,
                "error": f"worker rc={worker.returncode}",
                "stdout": worker.stdout[-500:],
                "stderr": worker.stderr[-2000:],
                "shim_log": shim_log,
            }
        device = json.loads(done[-1].split(" ", 1)[1])

        stat = json.loads(
            TokenClient("127.0.0.1", port, "drive/pod-a").stat()
        )
        pod = stat.get("pods", {}).get("drive/pod-a", {})
        grants = int(pod.get("grants", 0))
        verdict = {
            "gated": grants > 0,
            "grants": grants,
            "charged_ms": round(float(pod.get("charged_total_ms", 0.0)), 3),
            "device": device,
            "mem_used": pod.get("mem_used"),
            "shim_log": shim_log,
        }
        if cpu:
            # in-process CPU client: no dlopen'd plugin, nothing to hook —
            # this mode only proves the launch plumbing end-to-end
            verdict["note"] = ("cpu client is in-process (no dlopen); "
                               "gating requires the real accelerator plugin")
        return verdict
    finally:
        tokend_proc.kill()
        tokend_proc.wait()
        shutil.rmtree(workdir, ignore_errors=True)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true",
                        help="force the CPU PJRT plugin (smoke mode)")
    parser.add_argument("--timeout", type=float, default=600.0)
    args = parser.parse_args()
    verdict = drive(cpu=args.cpu, timeout=args.timeout)
    print(json.dumps(verdict))
    if "error" in verdict:
        return 1
    return 0 if (verdict["gated"] or args.cpu) else 1


if __name__ == "__main__":
    sys.exit(main())
