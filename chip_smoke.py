#!/usr/bin/env python3
"""Start the whole system on the chip, once, and check what comes out.

KubeShare's proposition end to end on one TPU, through the entry points a
user calls: the native token runtime built from the tracked sources, the
chip inventoried from the real device, two fractional pods placed on it by
the scheduler, ``configd`` writing the chip's share table, ``tpushare-tokend``
(+ per-pod ``tpushare-pmgr``) serving it, and pod A's ``ServingEngine`` — the
GQA flagship at full width, bf16, weights from a seed — answering seeded
requests token-gated while pod B's gated loop shares the chip.  Streams are
compared with the plain dense-cache decode, the flash kernel with the XLA
reference, and a flagship train step must lower to the kernel and learn.

    python chip_smoke.py               one chip, ONE JAX process (the driver's run)
    python chip_smoke.py --chips 4     replica fleet dp=2 x tp=2 vs one engine
    python chip_smoke.py --interposer  LD_PRELOAD shim gating a plain JAX child

One process holds the chip: every child this script starts (tokend, pmgr)
is a native binary that never touches JAX; ``--interposer`` keeps the parent
off JAX so its one child can open the device.  Without a TPU the script
fails before any phase.  Any failing phase raises — nothing here turns a
failure into a printed note.  Each phase prints one JSON line; the LAST
line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from kubeshare_tpu import constants  # noqa: E402  (no JAX behind this import)
from kubeshare_tpu.isolation.guard import apply_hbm_cap  # noqa: E402
from kubeshare_tpu.utils.compile_cache import (  # noqa: E402
    CACHE_ENV, configure_compile_cache)

# pod A's HBM share, applied to THIS process before JAX starts (the cap has
# to precede backend init) and checked against what the scheduler injects
# for pod A once it is placed
POD_A_MEM_FRACTION = 0.5

# A greedy stream may leave the reference only where the reference itself
# cannot tell the two tokens apart.  The lm_head product leaves the MXU in
# bf16 (8 significand bits), so every logit sits on a grid whose spacing at
# the top of this model's distribution (|z| in [4, 16)) is 2**-5 .. 2**-4,
# and with 32000 random-weight candidates the best two often share a grid
# point or sit on neighbouring ones.  Two correct programs that order the
# d_model=1024 sums differently (paged chunk, dense cache, bulk forward,
# verify span) may round a logit one grid point apart, and argmax then
# flips.  So a first divergence must lie within TIE_ULPS bf16 grid points,
# counted at the magnitude of the reference's best logit; anything wider
# is a wrong answer, not a tie.  (The chip showed 0-2; 4 leaves room for a
# rounding on each side of each candidate.)
TIE_ULPS = 4

# flash kernel vs the float32 XLA oracle: max abs error over max abs value
# of the oracle.  bf16 inputs and bf16 probabilities bound each output near
# 2**-8 relative per term (the chip showed 3e-3 .. 5e-3 over out, dq, dk,
# dv); 2**-6 leaves a factor of three and still fails a masking or scaling
# mistake, which shows as an error of order 1.
KERNEL_REL_BOUND = 2.0 ** -6


def bf16_ulp(x: float) -> float:
    """Spacing of bfloat16 values at magnitude ``x``."""
    return 2.0 ** (math.floor(math.log2(max(abs(x), 2.0 ** -126))) - 7)


@dataclass(frozen=True)
class Sizes:
    """Every size the phases use.  ``FULL`` is what the chip runs; a CPU
    rehearsal imports the phase functions and passes a tiny one."""

    model: Dict  # TransformerConfig kwargs of the serving model
    engine: Dict  # EngineConfig kwargs shared by both engines
    n_requests: int
    prompt_lens: Tuple[int, ...]
    new_tokens: Tuple[int, int]  # inclusive range of outputs per request
    kernel_shapes: Tuple[Tuple[int, int, int, int, int], ...]  # b,h,h_kv,s,d
    train_model: Dict
    train_batch: Tuple[int, int]  # batch, sequence
    interpret: bool = False  # Pallas interpret mode (CPU rehearsal only)


# The widest serving model the repository names: the GQA decode twin of the
# flagship training Transformer (docs/perf.md).
_FLAGSHIP = dict(d_model=1024, n_layers=8, n_heads=8, d_ff=4096,
                 vocab_size=32000)
FULL = Sizes(
    model=dict(_FLAGSHIP, n_kv_heads=2, max_seq_len=1024, positional="rope"),
    # a deployment's pool: 16 lanes, 1024-row requests, 8192 blocks of 16
    # rows (128 KiB each at this width: 1 GiB of KV)
    engine=dict(num_slots=16, block_size=16, num_blocks=8193,
                max_request_len=1024, prefill_chunk=128,
                mixed_prefill_budget=32),
    n_requests=32,
    # few distinct lengths (the dense reference compiles one program per
    # length), ragged against both the block and the chunk width
    prompt_lens=(32, 77, 150, 301, 512, 768),
    new_tokens=(16, 128),
    kernel_shapes=((2, 8, 8, 2048, 128), (2, 8, 2, 2048, 128)),
    train_model=dict(_FLAGSHIP, max_seq_len=2048, attention="auto"),
    train_batch=(2, 2048),
)


@dataclass
class Run:
    """What the phases hand each other."""

    sizes: Sizes
    chips: int
    workdir: str
    device: Optional[Dict] = None
    compile_stats: Optional["CompileStats"] = None
    inventory: List = field(default_factory=list)
    pods: Dict[str, Dict] = field(default_factory=dict)  # name -> env/ports
    tokend_port: int = 0
    config: object = None  # TransformerConfig
    params: object = None
    requests: List = field(default_factory=list)  # (rid, prompt, max_new)
    streams: Dict[str, Dict[str, List[int]]] = field(default_factory=dict)
    engine: object = None  # the engine kept alive for the co-tenant leg
    guard_a: object = None
    forward: object = None  # compiled dense forward (reference_logits)
    closers: List[Callable[[], None]] = field(default_factory=list)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


# ---------------------------------------------------------------------------
# device + compile-cache bookkeeping
# ---------------------------------------------------------------------------

def probe_device() -> Dict:
    """The device as JAX reports it.  First touch of JAX in the process."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def open_device(run: "Run") -> Dict:
    """First touch of JAX: pod A's HBM cap goes in before the backend
    starts, the device must be a TPU, the compile cache gets its place."""
    os.environ[constants.ENV_MEM_FRACTION] = f"{POD_A_MEM_FRACTION:.4f}"
    apply_hbm_cap()
    run.device = probe_device()
    require_tpu(run.device, run.chips)
    cache_dir = configure_compile_cache() or os.environ[CACHE_ENV]
    run.compile_stats = CompileStats()
    return {"device": run.device, "compile_cache": cache_dir}


def require_tpu(device: Dict, chips: int) -> None:
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {device['platform']!r} "
            f"({device['kind']}); there is no CPU fallback")
    if device["count"] != chips:
        raise SystemExit(
            f"chip_smoke: this mode drives {chips} chip(s), JAX sees "
            f"{device['count']}")


class CompileStats:
    """Counts what JAX's persistent compilation cache did, by listening to
    JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring

        self.requests = self.hits = self.writes = 0
        self.backend_seconds = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1  # recorded when a fresh compile is written

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_seconds += seconds

    def snapshot(self) -> Dict:
        return {"requests": self.requests, "cache_hits": self.hits,
                "cache_writes": self.writes,
                "compile_s": round(self.backend_seconds, 1)}

    def since(self, before: Dict) -> Dict:
        now = self.snapshot()
        return {k: round(now[k] - before[k], 1) for k in now}


def tokend_stat(run: Run) -> Dict:
    from kubeshare_tpu.isolation import TokenClient

    client = TokenClient("127.0.0.1", run.tokend_port, "chip-smoke/stat")
    try:
        return json.loads(client.stat())["pods"]
    finally:
        client.close()


# ---------------------------------------------------------------------------
# phase 1: the native runtime, from tracked sources only
# ---------------------------------------------------------------------------

def phase_native_build(run: Run) -> Dict:
    """``make -B`` against the TRACKED pjrt_c_api.h, so that what runs
    depends on committed files alone (native/build is git-ignored, and an
    installed tensorflow wheel would otherwise lend its header)."""
    from kubeshare_tpu.runtime import find_binary

    native = os.path.join(REPO, "native")
    started = time.time()
    proc = subprocess.run(
        ["make", "-B", "-C", native,
         f"PJRT_INC={os.path.join(native, 'third_party', 'xla')}"],
        capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"native build failed (rc {proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    built = [os.path.join(native, "build", name) for name in
             ("tpushare-tokend", "tpushare-pmgr", "libtpushim.so.1")]
    for path in built:
        if not os.path.isfile(path) or os.path.getmtime(path) < started - 1.0:
            raise RuntimeError(f"{path} was not rebuilt")
    if find_binary("tpushare-tokend") != built[0]:
        raise RuntimeError(
            f"the supervisor would launch {find_binary('tpushare-tokend')!r}"
            f", not the tokend just built")
    built = [os.path.relpath(path, REPO) for path in built]
    return {"built": built}


# ---------------------------------------------------------------------------
# phase 2: inventory from the real device
# ---------------------------------------------------------------------------

def phase_inventory(run: Run) -> Dict:
    import jax

    from kubeshare_tpu.cell.topology import (DEFAULT_HBM_BYTES,
                                             discover_local_chips)

    chips = discover_local_chips()
    if len(chips) != run.chips:
        raise RuntimeError(
            f"inventory has {len(chips)} chip(s), expected {run.chips}")
    for chip in chips:
        if chip.model not in DEFAULT_HBM_BYTES:
            raise RuntimeError(
                f"unknown device kind {chip.model!r} (chip {chip.uuid}): "
                f"not a TPU generation the cell model knows")
    stats = jax.local_devices()[0].memory_stats() or {}
    limit = int(stats.get("bytes_limit", 0))
    source = ("memory_stats.bytes_limit" if limit > 0
              and chips[0].memory == limit else "DEFAULT_HBM_BYTES table")
    run.inventory = chips
    return {"chips": [{"uuid": c.uuid, "model": c.model, "memory": c.memory,
                       "coords": c.coords} for c in chips],
            "device_kind": jax.local_devices()[0].device_kind,
            "memory_source": source}


# ---------------------------------------------------------------------------
# phase 3: control plane on that inventory
# ---------------------------------------------------------------------------

def phase_control_plane(run: Run) -> Dict:
    """Scheduler places two fractional pods on the chip; configd writes the
    share table; the per-chip supervisor starts tokend + one pmgr per pod."""
    from kubeshare_tpu.cell.topology import generate_tpu_topology_config
    from kubeshare_tpu.cluster.api import FakeClock, Node, Pod, PodPhase
    from kubeshare_tpu.cluster.fake import FakeCluster
    from kubeshare_tpu.configd import ConfigDaemon
    from kubeshare_tpu.runtime import ChipSupervisor
    from kubeshare_tpu.scheduler import KubeShareScheduler, SchedulerEngine
    from kubeshare_tpu.utils.net import free_port, wait_listening

    chip = run.inventory[0]
    node = socket.gethostname()
    cluster = FakeCluster()
    cluster.add_node(Node(node, {constants.NODE_LABEL_FILTER: "true"}))
    plugin = KubeShareScheduler(
        generate_tpu_topology_config([(node, chip.model, len(run.inventory))]),
        cluster, lambda n: list(run.inventory) if n == node else [],
        clock=FakeClock(0.0))
    scheduler = SchedulerEngine(plugin, cluster, plugin.clock)
    # pod A serves and may burst to the whole chip; pod B is clamped to
    # its half.  Both ask for half the compute; A asks for half the HBM.
    wanted = {
        "serve-a": {"limit": "1.0",
                    "mem": int(chip.memory * POD_A_MEM_FRACTION)},
        "cotenant-b": {"limit": "0.5", "mem": chip.memory // 4},
    }
    for name, want in wanted.items():
        cluster.create_pod(Pod(
            name=name,
            labels={constants.POD_GPU_REQUEST: "0.5",
                    constants.POD_GPU_LIMIT: want["limit"],
                    constants.POD_GPU_MEMORY: str(want["mem"])},
            scheduler_name=constants.SCHEDULER_NAME))
    for result in scheduler.run_until_idle():
        pod = cluster.get_pod(*result.pod_key.split("/"))
        if pod is None or not pod.is_bound():
            raise RuntimeError(f"{result.pod_key} not placed: {result}")
        if pod.annotations[constants.POD_GPU_UUID] != chip.uuid:
            raise RuntimeError(f"{result.pod_key} placed off the chip: "
                               f"{pod.annotations}")
        cluster.set_pod_phase(pod.namespace, pod.name, PodPhase.RUNNING)
        run.pods[pod.name] = {
            "key": pod.get_env(constants.ENV_POD_NAME),
            "port": int(pod.get_env(constants.ENV_POD_MANAGER_PORT)),
            "mem_fraction": float(pod.get_env(constants.ENV_MEM_FRACTION)),
            "limit": wanted[pod.name]["limit"],
        }
    if set(run.pods) != set(wanted):
        raise RuntimeError(f"placed {sorted(run.pods)}, wanted "
                           f"{sorted(wanted)}")
    injected = run.pods["serve-a"]["mem_fraction"]
    if abs(injected - POD_A_MEM_FRACTION) > 1e-3:
        raise RuntimeError(
            f"scheduler injected {constants.ENV_MEM_FRACTION}={injected} "
            f"for pod A; this process capped itself at {POD_A_MEM_FRACTION}")

    config_dir = os.path.join(run.workdir, "config")
    port_dir = os.path.join(run.workdir, "podmanagerport")
    ConfigDaemon(node, cluster=cluster, config_dir=config_dir,
                 port_dir=port_dir).sync()
    with open(os.path.join(config_dir, chip.uuid)) as f:
        table = f.read().split("\n")
    if int(table[0]) != len(wanted):
        raise RuntimeError(f"share table rows: {table}")

    run.tokend_port = free_port()
    supervisor = ChipSupervisor(chip.uuid, config_dir=config_dir,
                                port_dir=port_dir,
                                tokend_port=run.tokend_port,
                                poll_interval=0.2)
    supervisor.start()
    run.closers.append(supervisor.stop)
    wait_listening(run.tokend_port)
    for pod in run.pods.values():
        wait_listening(pod["port"])
    return {"node": node, "chip": chip.uuid, "pods": run.pods,
            "share_table": [row for row in table[1:] if row],
            "tokend_port": run.tokend_port}


def pod_guard(run: Run, name: str):
    """A guard bound to one pod's identity, reached the way a pod reaches
    it: through its own pmgr port, under the scheduler-injected name.  An
    unreachable broker is a failure here, never "running ungated"."""
    from kubeshare_tpu.isolation import ExecutionGuard, TokenClient

    pod = run.pods[name]
    client = TokenClient("127.0.0.1", pod["port"], pod["key"])
    client.ping()
    run.closers.append(client.close)
    guard = ExecutionGuard(client=client, from_env=False)
    if not guard.gated:
        raise RuntimeError(f"guard for {name} is not gated")
    return guard


# ---------------------------------------------------------------------------
# phase 4: the serving leg
# ---------------------------------------------------------------------------

def build_model(run: Run) -> None:
    import jax
    import jax.numpy as jnp

    from kubeshare_tpu.models.transformer import (TransformerConfig,
                                                  transformer_init)

    run.config = TransformerConfig(dtype=jnp.bfloat16, **run.sizes.model)
    run.params = transformer_init(jax.random.PRNGKey(0), run.config)
    jax.block_until_ready(run.params)


def seeded_requests(sizes: Sizes, vocab: int) -> List:
    """(rid, prompt, max_new) of mixed lengths.  Every fourth prompt repeats
    a short phrase, so the n-gram drafter has something to find and the
    speculative device loop something to verify."""
    import numpy as np

    rng = np.random.default_rng(0)
    out = []
    for i in range(sizes.n_requests):
        length = int(sizes.prompt_lens[i % len(sizes.prompt_lens)])
        if i % 4 == 3:
            phrase = rng.integers(0, vocab, 8)
            prompt = np.tile(phrase, -(-length // 8))[:length]
        else:
            prompt = rng.integers(0, vocab, length)
        max_new = int(rng.integers(sizes.new_tokens[0],
                                   sizes.new_tokens[1] + 1))
        out.append((f"r{i:02d}", prompt.astype(np.int32), max_new))
    return out


def serve(run: Run, engine, tag: str) -> Tuple[Dict[str, List[int]], Dict]:
    """Submit every request, drain, and hold the engine to its contract:
    the asked number of tokens each, nothing compiled after warmup."""
    from kubeshare_tpu.serving import Request

    warm = engine.compile_counts()
    for rid, prompt, max_new in run.requests:
        engine.submit(Request(f"{tag}-{rid}", prompt, max_new))
    start = time.monotonic()
    results = engine.run()
    seconds = time.monotonic() - start
    streams = {}
    for rid, _, max_new in run.requests:
        result = results[f"{tag}-{rid}"]
        if not result.done or len(result.tokens) != max_new:
            raise RuntimeError(
                f"{tag}-{rid}: {len(result.tokens)} of {max_new} tokens, "
                f"done={result.done}")
        streams[rid] = [int(t) for t in result.tokens]
    after = engine.compile_counts()
    if after != warm:
        raise RuntimeError(f"{tag}: compiled after warmup: {warm} -> {after}")
    tokens = sum(len(s) for s in streams.values())
    return streams, {"requests": len(streams), "tokens": tokens,
                     "serve_s": round(seconds, 2), "programs": after}


def serving_pass(run: Run, tag: str, **engine_flags) -> Tuple[object, Dict]:
    """One engine under pod A's guard: warm, serve, check the gating."""
    import jax

    from kubeshare_tpu.serving import EngineConfig, ServingEngine

    ec = EngineConfig(**run.sizes.engine, **engine_flags)
    guard = run.guard_a
    before = tokend_stat(run)[run.pods["serve-a"]["key"]]
    engine = ServingEngine(run.params, run.config, ec, guard=guard)
    t0 = time.monotonic()
    engine.warmup()
    warmup_s = time.monotonic() - t0
    acquired = guard.tokens_acquired
    run.streams[tag], detail = serve(run, engine, tag)
    after = tokend_stat(run)[run.pods["serve-a"]["key"]]
    charged = after["charged_total_ms"] - before["charged_total_ms"]
    if not (guard.gated and guard.tokens_acquired > acquired and charged > 0):
        raise RuntimeError(
            f"{tag}: not gated: gated={guard.gated} tokens "
            f"{acquired}->{guard.tokens_acquired} charged {charged} ms")
    stats = jax.local_devices()[0].memory_stats() or {}
    detail.update(
        warmup_s=round(warmup_s, 1),
        tokens_acquired=guard.tokens_acquired - acquired,
        charged_ms=round(charged, 1),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        dispatches={"prefill_chunks": engine.prefill_chunks,
                    "decode_steps": engine.decode_steps,
                    "mixed_steps": engine.mixed_steps,
                    "verify_steps": engine.verify_steps,
                    "loop_launches": engine.loop_launches,
                    "loop_units": engine.loop_units,
                    "spec_loop_launches": engine.spec_loop_launches,
                    "spec_loop_units": engine.spec_loop_units,
                    "drafted": sum(engine.spec_drafted.values()),
                    "accepted": sum(engine.spec_accepted.values()),
                    "prefix_hit_tokens": engine.prefix_hit_tokens,
                    "loop_exits": {k: v for k, v in
                                   engine.loop_exit_reasons.items() if v}})
    return engine, detail


def phase_serve_default(run: Run) -> Dict:
    """EngineConfig's defaults: mixed batching, prefix cache, span of 4."""
    build_model(run)
    run.requests = seeded_requests(run.sizes, run.config.vocab_size)
    run.guard_a = pod_guard(run, "serve-a")
    engine, detail = serving_pass(run, "default")
    del engine  # drop one engine before building the next
    gc.collect()  # (engine <-> allocator hooks form a cycle)
    return detail


def phase_serve_device_loop(run: Run) -> Dict:
    """The same requests through the device-resident K-step loop: one
    lax.while_loop launch covers up to four decode spans."""
    engine, detail = serving_pass(run, "device_loop", steps_per_launch=4)
    if detail["dispatches"]["loop_launches"] == 0:
        raise RuntimeError(f"no device-loop launch ran: {detail}")
    del engine
    gc.collect()
    detail["vs_default"] = compare_streams(
        run, run.streams["device_loop"], run.streams["default"])
    return detail


def phase_serve_spec_loop(run: Run) -> Dict:
    """...and through verify-in-loop speculation with the pending-lane
    admission ring (the flags of examples/serve_fractional.py plus the
    ring).  This engine stays for the co-tenant leg."""
    engine, detail = serving_pass(
        run, "spec_loop", steps_per_launch=4, speculative=True,
        admission_ring=2)
    if detail["dispatches"]["spec_loop_launches"] == 0:
        raise RuntimeError(f"no speculative device-loop launch ran: {detail}")
    run.engine = engine
    detail["vs_default"] = compare_streams(
        run, run.streams["spec_loop"], run.streams["default"])
    return detail


# ---------------------------------------------------------------------------
# phase 5: the plain path
# ---------------------------------------------------------------------------

def dense_streams(run: Run) -> Dict[str, List[int]]:
    """models/decoding.py's dense-cache greedy decode: no paging, no
    engine.  One program per prompt length (requests of a length run as
    one batch, decoded to the longest output and cut)."""
    import jax
    import numpy as np

    from kubeshare_tpu.models.decoding import greedy_decode

    by_len: Dict[int, List] = {}
    for request in run.requests:
        by_len.setdefault(len(request[1]), []).append(request)
    longest = max(r[2] for r in run.requests)
    config = run.config
    # weights ride as an argument: closed over, they would be baked into
    # every program as constants
    decode = jax.jit(lambda params, prompts: greedy_decode(
        params, config, prompts, longest))
    streams = {}
    for group in by_len.values():
        out = np.asarray(decode(run.params,
                                np.stack([r[1] for r in group])))
        for (rid, _, max_new), row in zip(group, out):
            streams[rid] = [int(t) for t in row[:max_new]]
    return streams


def reference_logits(run: Run, tokens) -> "np.ndarray":
    """Float32 logits the dense forward gives for the token after
    ``tokens``: one padded full-sequence pass (causal, so the padding
    cannot reach back), one compiled shape for every query."""
    import jax
    import numpy as np

    from kubeshare_tpu.models.transformer import transformer_apply

    if run.forward is None:
        config = run.config
        run.forward = jax.jit(lambda params, t, last: transformer_apply(
            params, t, config)[0, last].astype("float32"))
    padded = np.zeros((1, run.config.max_seq_len), np.int32)
    padded[0, :len(tokens)] = tokens
    return np.asarray(run.forward(run.params, padded, len(tokens) - 1))


def compare_streams(run: Run, got: Dict[str, List[int]],
                    want: Dict[str, List[int]]) -> Dict:
    """Share of identical streams, and every first divergence held to a
    tie in the reference's own logits (see TIE_ULPS).  Reports the first
    divergence in full and the widest gap over all of them."""
    import numpy as np

    prompts = {rid: prompt for rid, prompt, _ in run.requests}
    identical, divergences = 0, []
    for rid, expect in want.items():
        mine = got[rid]
        if mine == expect:
            identical += 1
            continue
        at = next(i for i, (a, b) in enumerate(zip(mine, expect)) if a != b)
        logits = reference_logits(
            run, np.concatenate([prompts[rid], expect[:at]]).astype(np.int32))
        top2 = np.sort(logits)[-2:]
        ulp = bf16_ulp(float(top2[1]))
        gap = abs(float(logits[expect[at]] - logits[mine[at]]))
        divergences.append({
            "rid": rid, "at": at, "got": mine[at], "want": expect[at],
            "top_logit": round(float(top2[1]), 4),
            "top2_gap": round(float(top2[1] - top2[0]), 4),
            "pick_gap": round(gap, 4), "pick_gap_ulps": round(gap / ulp, 2)})
        if gap > TIE_ULPS * ulp:
            raise RuntimeError(
                f"{rid} leaves the reference at token {at} where the "
                f"reference is not tied: {divergences[-1]} (tolerance "
                f"{TIE_ULPS} bf16 ulps = {TIE_ULPS * ulp})")
    return {"identical": identical, "of": len(want),
            "share_identical": round(identical / len(want), 4),
            "divergences": len(divergences),
            "max_pick_gap_ulps": max(
                (d["pick_gap_ulps"] for d in divergences), default=0.0),
            "first_divergence": divergences[0] if divergences else None}


def phase_reference(run: Run) -> Dict:
    run.streams["dense"] = dense_streams(run)
    return {tag: compare_streams(run, run.streams[tag], run.streams["dense"])
            for tag in ("default", "device_loop", "spec_loop")}


# ---------------------------------------------------------------------------
# phase 6: the co-tenant
# ---------------------------------------------------------------------------

def phase_cotenant(run: Run) -> Dict:
    """Pod B — its own identity, client and guard — runs a gated matmul
    chain on a thread while pod A serves the requests again."""
    import jax
    import jax.numpy as jnp

    guard_b = pod_guard(run, "cotenant-b")
    n = 4096
    x = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def chain(a):  # 8 matmuls, 1.1 TFLOP: milliseconds of chip per step
        for _ in range(8):
            a = (a @ a) * (1.0 / n)
        return a

    jax.block_until_ready(chain(x))  # compile outside the gated window
    stop, state = threading.Event(), {"steps": 0, "error": None}

    def tenant_b() -> None:
        try:
            while not stop.is_set():
                guard_b.acquire()
                t0 = time.monotonic()
                jax.block_until_ready(chain(x))
                guard_b.charge((time.monotonic() - t0) * 1e3)
                state["steps"] += 1
        except Exception as e:  # re-raised on the main thread below
            state["error"] = e
        finally:
            guard_b.finish()

    before = tokend_stat(run)
    thread = threading.Thread(target=tenant_b, name="pod-b", daemon=True)
    thread.start()
    try:
        streams, detail = serve(run, run.engine, "cotenant")
    finally:
        stop.set()
        thread.join(timeout=60)
    if thread.is_alive() or state["error"] is not None:
        raise RuntimeError(f"pod B's loop failed: alive={thread.is_alive()} "
                           f"error={state['error']!r}")
    after = tokend_stat(run)
    tenants = {}
    for name, pod in run.pods.items():
        a, b = after[pod["key"]], before[pod["key"]]
        tenants[name] = {
            "grants": a["grants"] - b["grants"],
            "charged_ms": round(a["charged_total_ms"]
                                - b["charged_total_ms"], 1),
            "duty": round(a["share"], 4), "limit": a["limit"],
            "request": a["request"]}
        if tenants[name]["grants"] <= 0 or tenants[name]["charged_ms"] <= 0:
            raise RuntimeError(f"{name} was not granted and charged: "
                               f"{tenants[name]}")
    detail.update(tenants=tenants, pod_b_steps=state["steps"],
                  vs_solo=compare_streams(run, streams,
                                          run.streams["spec_loop"]))
    run.engine = None  # the last engine goes before the training leg
    gc.collect()
    return detail


# ---------------------------------------------------------------------------
# phase 7: kernel and train step
# ---------------------------------------------------------------------------

def phase_kernels(run: Run) -> Dict:
    """flash_attention forward and backward against the float32 XLA
    oracle; the lowered text must hold the Pallas call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeshare_tpu.ops.attention import (attention_reference,
                                             flash_attention)

    interpret = run.sizes.interpret
    rows = []
    for b, h, h_kv, s, d in run.sizes.kernel_shapes:
        keys = jax.random.split(jax.random.PRNGKey(s + h_kv), 4)
        q = jax.random.normal(keys[0], (b, h, s, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, h_kv, s, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, h_kv, s, d), jnp.bfloat16)
        g = jax.random.normal(keys[3], (b, h, s, d), jnp.bfloat16)

        def kernel(q, k, v):
            return flash_attention(q, k, v, causal=True,
                                   interpret=interpret)

        def oracle(q, k, v):
            return attention_reference(q, k, v, causal=True)

        def fwd_bwd(fn, *args):
            out, vjp = jax.vjp(fn, *args)
            return (out,) + vjp(g.astype(out.dtype))

        step = jax.jit(lambda q, k, v: fwd_bwd(kernel, q, k, v))
        if not interpret and "tpu_custom_call" not in step.lower(
                q, k, v).as_text():
            raise RuntimeError(
                f"flash_attention at {(b, h, h_kv, s, d)} lowered without "
                f"the Pallas kernel (demoted to the XLA reference)")
        got = step(q, k, v)
        want = jax.jit(lambda q, k, v: fwd_bwd(oracle, q, k, v))(
            *(t.astype(jnp.float32) for t in (q, k, v)))
        errors = {}
        for name, a, e in zip(("out", "dq", "dk", "dv"), got, want):
            a, e = np.asarray(a, np.float32), np.asarray(e, np.float32)
            if not np.isfinite(a).all():
                raise RuntimeError(f"{name} not finite at {(b, h, h_kv, s, d)}")
            errors[name] = float(np.abs(a - e).max() / np.abs(e).max())
            if errors[name] > KERNEL_REL_BOUND:
                raise RuntimeError(
                    f"flash {name} at {(b, h, h_kv, s, d)}: relative max "
                    f"error {errors[name]:.4g} over {KERNEL_REL_BOUND:.4g}")
        rows.append({"shape": [b, h, h_kv, s, d],
                     "rel_max_err": {k: round(v, 5) for k, v in errors.items()}})
    return {"bound": KERNEL_REL_BOUND, "shapes": rows}


def phase_train(run: Run) -> Dict:
    """Three gated steps of the flagship train step, attention="auto": the
    kernel must be in the step's program and the loss must not rise."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeshare_tpu.models.transformer import (TransformerConfig,
                                                  transformer_apply,
                                                  transformer_init)
    from kubeshare_tpu.parallel.train import make_train_step

    run.params = None  # the serving weights are done
    config = TransformerConfig(dtype=jnp.bfloat16, **run.sizes.train_model)
    batch, seq = run.sizes.train_batch
    init_state, train_step = make_train_step(
        lambda p, t: transformer_apply(p, t, config))
    state = init_state(transformer_init(jax.random.PRNGKey(1), config))
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, config.vocab_size, (batch, seq)),
                         jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    if not run.sizes.interpret and "tpu_custom_call" not in train_step.lower(
            state, tokens, targets).as_text():
        raise RuntimeError('train step with attention="auto" lowered '
                           "without the Pallas kernel")
    guard = run.guard_a
    acquired, losses = guard.tokens_acquired, []
    for _ in range(3):
        guard.acquire()
        t0 = time.monotonic()
        state, loss = train_step(state, tokens, targets)
        loss = float(jax.block_until_ready(loss))
        guard.charge((time.monotonic() - t0) * 1e3)
        losses.append(loss)
    guard.finish()
    if not all(np.isfinite(losses)) or any(
            b > a for a, b in zip(losses, losses[1:])):
        raise RuntimeError(f"train losses not finite and non-rising: {losses}")
    if guard.tokens_acquired <= acquired:
        raise RuntimeError("train steps ran without a token")
    stats = jax.local_devices()[0].memory_stats() or {}
    return {"losses": [round(x, 4) for x in losses],
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


# ---------------------------------------------------------------------------
# phase 8: did the HBM cap bind?
# ---------------------------------------------------------------------------

def phase_hbm_cap(run: Run) -> Dict:
    """apply_hbm_cap ran before JAX started (see main); say what the
    runtime made of it.  Whether it binds is a finding, not a failure."""
    import jax

    from kubeshare_tpu.cell.topology import DEFAULT_HBM_BYTES

    stats = jax.local_devices()[0].memory_stats() or {}
    limit = int(stats.get("bytes_limit", 0))
    nominal = DEFAULT_HBM_BYTES[run.inventory[0].model]
    target = int(POD_A_MEM_FRACTION * nominal)
    return {"fraction_applied": POD_A_MEM_FRACTION,
            "XLA_PYTHON_CLIENT_MEM_FRACTION":
                os.environ.get("XLA_PYTHON_CLIENT_MEM_FRACTION"),
            "bytes_limit": limit, "chip_hbm_nominal": nominal,
            "half_of_chip": target,
            "cap_binds": 0 < limit <= int(target * 1.02)}


# ---------------------------------------------------------------------------
# --chips 4: replicas behind the router, each tensor-parallel over two chips
# ---------------------------------------------------------------------------

def phase_fleet(run: Run) -> Dict:
    import jax

    from kubeshare_tpu.parallel.mesh import MeshSpec
    from kubeshare_tpu.serving import EngineConfig, ReplicaFleet

    build_model(run)
    run.requests = seeded_requests(run.sizes, run.config.vocab_size)
    ec = EngineConfig(**run.sizes.engine, mesh_spec=MeshSpec(dp=2, tp=2, sp=1))
    fleet = ReplicaFleet(run.params, run.config, ec, replicas=2)
    t0 = time.monotonic()
    fleet.warmup()
    warmup_s = time.monotonic() - t0
    placement = {}
    for handle in fleet.replicas:
        engine = handle.engine
        param_devices = sorted({
            d.id for leaf in jax.tree.leaves(engine.params)
            for d in leaf.devices()})
        pool_devices = sorted(d.id for d in engine.pool.k.devices())
        placement[handle.name] = {"pool_k_sharding": str(engine.pool.k.sharding),
                                  "pool_devices": pool_devices,
                                  "param_devices": param_devices}
        if len(pool_devices) != 2 or param_devices != pool_devices:
            raise RuntimeError(f"replica {handle.name} is not on its own "
                               f"two chips: {placement[handle.name]}")
    groups = [tuple(p["pool_devices"]) for p in placement.values()]
    if len(set(groups)) != len(groups) or len({d for g in groups for d in g}) != 4:
        raise RuntimeError(f"replicas share chips: {placement}")
    memory = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.local_devices()}
    run.streams["fleet"], detail = serve(run, fleet, "fleet")
    owners = {}
    for rid, _, _ in run.requests:
        owner = fleet.owner_of(f"fleet-{rid}")
        owners[owner] = owners.get(owner, 0) + 1
    if len(owners) != 2:
        raise RuntimeError(f"router used {owners}, not both replicas")
    detail.update(warmup_s=round(warmup_s, 1), placement=placement,
                  bytes_in_use_after_warmup=memory, requests_by_replica=owners)
    return detail


def phase_single_engine(run: Run) -> Dict:
    """The comparison: the same requests through one single-device engine,
    then both against the dense reference as in the one-chip run."""
    from kubeshare_tpu.serving import EngineConfig, ServingEngine

    engine = ServingEngine(run.params, run.config,
                           EngineConfig(**run.sizes.engine))
    t0 = time.monotonic()
    engine.warmup()
    warmup_s = time.monotonic() - t0
    run.streams["single"], detail = serve(run, engine, "single")
    del engine
    gc.collect()
    run.streams["dense"] = dense_streams(run)
    detail.update(
        warmup_s=round(warmup_s, 1),
        fleet_vs_single=compare_streams(run, run.streams["fleet"],
                                        run.streams["single"]),
        fleet_vs_dense=compare_streams(run, run.streams["fleet"],
                                       run.streams["dense"]),
        single_vs_dense=compare_streams(run, run.streams["single"],
                                        run.streams["dense"]))
    return detail


# ---------------------------------------------------------------------------
# --interposer: the preload path against the real runtime
# ---------------------------------------------------------------------------

def phase_interposer(run: Run) -> Dict:
    """examples/shim_drive.py: a plain JAX child under LD_PRELOAD, gated by
    a live tokend.  The parent stays off JAX; the device is the child's."""
    from examples.shim_drive import drive

    verdict = drive()
    if "error" in verdict or not verdict["gated"] or verdict["grants"] <= 0:
        raise RuntimeError(f"the interposer did not gate: {verdict}")
    run.device = verdict["device"]
    require_tpu(run.device, 1)
    return verdict


PHASES_ONE_CHIP = [
    ("native_build", phase_native_build),
    ("inventory", phase_inventory),
    ("control_plane", phase_control_plane),
    ("serve_default", phase_serve_default),
    ("serve_device_loop", phase_serve_device_loop),
    ("serve_spec_loop", phase_serve_spec_loop),
    ("reference", phase_reference),
    ("cotenant", phase_cotenant),
    ("kernels", phase_kernels),
    ("train", phase_train),
    ("hbm_cap", phase_hbm_cap),
]
PHASES_FOUR_CHIPS = [
    ("fleet", phase_fleet),
    ("single_engine", phase_single_engine),
]
PHASES_INTERPOSER = [
    ("native_build", phase_native_build),
    ("interposer", phase_interposer),
]


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the replica fleet (dp=2 x tp=2) and "
                             "the single engine it is compared with")
    parser.add_argument("--interposer", action="store_true",
                        help="only the LD_PRELOAD interposer leg (the "
                             "parent stays off JAX)")
    args = parser.parse_args(argv)

    run = Run(sizes=FULL, chips=args.chips,
              workdir=tempfile.mkdtemp(prefix="chip-smoke-"))
    try:
        if args.interposer:
            phases = PHASES_INTERPOSER
        else:
            emit(phase="start", **open_device(run))
            phases = PHASES_FOUR_CHIPS if args.chips == 4 else PHASES_ONE_CHIP
        stats = run.compile_stats  # None when the parent stays off JAX
        started = time.monotonic()
        for name, phase in phases:
            t0 = time.monotonic()
            before = stats.snapshot() if stats else None
            detail = phase(run)
            emit(phase=name, seconds=round(time.monotonic() - t0, 1),
                 compile=stats.since(before) if stats else None, **detail)
        emit(phase="done", seconds=round(time.monotonic() - started, 1),
             compile=stats.snapshot() if stats else None)
    finally:
        for close in reversed(run.closers):
            close()
        shutil.rmtree(run.workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": run.device["platform"], "kind": run.device["kind"],
        "count": run.device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
