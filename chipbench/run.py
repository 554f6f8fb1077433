"""One run of one cell: ``python3 -m chipbench.run --workload <cell> --seed
<n> --seconds <s> --trace <0|1>``.

One process.  It fails at once unless JAX sees a TPU and as many chips as
the cell asks for, builds the native runtime when ``native/build`` lacks it,
places the pods with the real scheduler, configd and supervisor, makes the
weights from the seed, warms the engine (all of that is ``setup_s``), drives
the cell's traffic for ``--seconds``, checks what was served against the
plain reference and the token runtime's guarantees, and prints one JSON
line last.  See README.md for the files each cell is made of.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
import traceback
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import metrics, roofline, system, traffic  # noqa: E402

STATE_DIR = os.path.join(HERE, ".state")  # git-ignored: control-plane files, traces
# what a configuration's file may name, and what stands where it names none:
# its plain reference, the maker of its weights, its count of bytes
MODULES = {"reference": "chipbench.reference", "weights": "chipbench.weights",
           "roofline": "chipbench.roofline"}
TRACE_SECONDS = 5.0  # the tail of the window that a --trace 1 run traces
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def process_age_s() -> float:
    """Seconds since this process was started, by the kernel's record."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


def host_cpu_s() -> Dict[str, float]:
    """The machine's CPU seconds so far, by the kernel's record."""
    with open("/proc/stat") as f:
        ticks = [int(x) for x in f.readline().split()[1:9]]
    names = ("user", "nice", "system", "idle", "iowait", "irq", "softirq",
             "steal")
    return {n: t / os.sysconf("SC_CLK_TCK") for n, t in zip(names, ticks)}


class StallWatch:
    """Says where a long ``engine.step()`` was: a run whose window holds a
    stall of seconds reads far from the others, and nothing in the program
    names the cause.  A thread looks four times a second; once a step has
    lasted ``LONG_S`` it keeps every thread's Python stack, and the loop adds
    the step's wall, thread and process CPU time and the machine's CPU
    seconds over it (from the look before it began, at most 0.25 s early).
    An earlier line, never a metric."""

    LONG_S = 1.0

    def __init__(self) -> None:
        self.long_steps: List[Dict] = []
        self._started: Optional[float] = None
        self._stacks: Optional[Dict[str, List[str]]] = None
        self._stop = threading.Event()
        self._host = [(time.monotonic(), host_cpu_s())]
        self._thread = threading.Thread(target=self._look, name="stall-watch",
                                        daemon=True)
        self._thread.start()

    def _look(self) -> None:
        while not self._stop.wait(0.25):
            self._host.append((time.monotonic(), host_cpu_s()))
            del self._host[:-80]  # the last 20 s
            started = self._started
            if (started is None or self._stacks is not None
                    or time.monotonic() - started < self.LONG_S):
                continue
            names = {t.ident: t.name for t in threading.enumerate()}
            self._stacks = {
                names.get(ident, str(ident)): [
                    f"{os.path.basename(f.filename)}:{f.lineno} {f.name}"
                    for f in traceback.extract_stack(frame)[-8:]]
                for ident, frame in sys._current_frames().items()
                if ident != threading.get_ident()}

    def begin(self) -> None:
        self._stacks = None
        self._mark = (time.monotonic(), time.thread_time(),
                      time.process_time())
        self._started = self._mark[0]

    def end(self, at_s: float, index: int, kind: Optional[str]) -> None:
        self._started = None
        wall = time.monotonic() - self._mark[0]
        if wall < self.LONG_S:
            return
        host = host_cpu_s()
        before = [h for t, h in self._host if t <= self._mark[0]][-1]
        self.long_steps.append({
            "i": index, "at_s": round(at_s, 3), "kind": kind,
            "wall_s": round(wall, 3),
            "thread_cpu_s": round(time.thread_time() - self._mark[1], 3),
            "process_cpu_s": round(time.process_time() - self._mark[2], 3),
            "host_cpu_s": {k: round(v - before[k], 2)
                           for k, v in host.items()},
            "stacks": self._stacks})

    def close(self) -> None:
        self._stop.set()
        self._thread.join()


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def config_modules(config_file: Dict) -> Dict[str, str]:
    """The names of a configuration's three modules (README.md says what
    each must expose); a file that names none gets the repository's block."""
    return {kind: config_file.get(kind, default)
            for kind, default in MODULES.items()}


def cell_module(cell: Dict, kind: str):
    return importlib.import_module(cell["modules"][kind])


def load_cell(name: str, benchmark: Optional[Dict] = None,
              root: str = REPO) -> Dict:
    """A cell, from ``BENCHMARK.json`` and the files its names point to."""
    bench = benchmark or load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = dict(cells[name])
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cell["config_file"] = load_json(root, config["file"])
    cell["modules"] = config_modules(cell["config_file"])
    base = os.path.dirname(os.path.dirname(os.path.join(root, config["file"])))
    cell["mix"] = traffic.load_mix(cell["traffic"],
                                   os.path.join(base, "traffic"))
    params_path = os.path.join(base, "cells", f"{name}.json")
    cell["params"] = (load_json(params_path)
                      if os.path.isfile(params_path) else {})
    cell["end_to_end"] = [m for m in bench["end_to_end"]
                          if name in m.get("workloads", [name])]
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if name in m.get("workloads", [name])]
    cell["metric_dir"] = os.path.join(base, "layer_metrics")
    return cell


class CompileCounter:
    """Backend compiles, by JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


def _delta(after: Dict, before: Dict) -> Dict:
    return {k: (_delta(v, before[k]) if isinstance(v, dict)
                else v - before[k]) for k, v in after.items()}


def scored(record: Dict) -> List[Dict]:
    """The window's own requests: those due before it closed."""
    return [e for e in record["sent"].values() if e["scored"]]


class Session:
    """The system under test, stood up once; ``measure`` drives a window."""

    def __init__(self, cell: Dict, seed: int, require_tpu: bool = True,
                 inventory=None) -> None:
        self.cell, self.cfg, self.mix = cell, cell["config_file"], cell["mix"]
        self.tc = self.cfg["transformer_config"]
        self.timeline: Dict[str, float] = {}
        t = time.monotonic()

        def lap(name: str) -> None:
            nonlocal t
            now = time.monotonic()
            self.timeline[name] = round(now - t, 3)
            t = now

        # pod A's HBM share goes into the environment before JAX starts,
        # as the scheduler's injected variable would in a pod
        from kubeshare_tpu import constants
        from kubeshare_tpu.isolation.guard import apply_hbm_cap
        from kubeshare_tpu.utils.compile_cache import configure_compile_cache

        os.environ[constants.ENV_MEM_FRACTION] = \
            f"{self.cfg['pod']['gpu_mem']:.4f}"
        apply_hbm_cap()
        import jax

        devices = jax.devices()
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind, "count": len(devices)}
        if require_tpu:
            if self.device["platform"] != "tpu":
                raise SystemExit(
                    f"chipbench: needs a TPU, JAX found "
                    f"{self.device['platform']!r}; there is no CPU fallback")
            if self.device["count"] != cell["chips"]:
                raise SystemExit(
                    f"chipbench: {cell['name']} asks for {cell['chips']} "
                    f"chip(s), JAX sees {self.device['count']}")
            roofline.peaks(self.device["kind"])  # unknown kind: an error
        self.cache_dir = configure_compile_cache()
        if self.cache_dir is not None:
            # the cache is this checkout's own: keep every program, or a
            # second cell's programs push the first's out
            jax.config.update("jax_compilation_cache_max_size", -1)
        else:
            self.cache_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        self.compiles = CompileCounter()
        lap("import_jax_s")

        self.timeline["native_build_s"] = round(system.ensure_native(), 3)
        if inventory is None:
            from kubeshare_tpu.cell.topology import discover_local_chips

            inventory = discover_local_chips()
        pods = [self.cfg["pod"]]
        if self.mix.get("cotenant"):
            pods.append(self.mix["cotenant"])
        shutil.rmtree(STATE_DIR, ignore_errors=True)
        os.makedirs(STATE_DIR)
        self.plane = system.ControlPlane(inventory, pods, STATE_DIR)
        self.cotenant = None
        self.seed = seed
        try:
            self._stand_up(lap)
        except BaseException:
            # a set-up that fails (a pool that is not what its count says, a
            # pod placed wrong) leaves no native child behind
            self.close()
            raise

    def _stand_up(self, lap) -> None:
        """Pod B, the weights, pod A's engine, warm: the rest of set-up."""
        import jax

        a = self.cfg["pod"]
        injected = self.plane.pods[a["name"]]["mem_fraction"]
        if abs(injected - a["gpu_mem"]) > 1e-3:
            raise RuntimeError(f"scheduler injected mem fraction {injected} "
                               f"for pod A, its file says {a['gpu_mem']}")
        lap("control_plane_s")

        if self.mix.get("cotenant"):
            # pod B runs from here on, so that tokend's decayed share of
            # it has settled at its limit before the window opens
            spec = self.mix["cotenant"]
            self.cotenant = system.Cotenant(self.plane.guard(spec["name"]),
                                            spec)
            self.cotenant.start()
            lap("cotenant_start_s")

        self.params = cell_module(self.cell, "weights").make_weights(
            self.seed, self.tc)
        jax.block_until_ready(self.params)
        lap("weights_s")

        self.annotate = jax.profiler.TraceAnnotation
        self.guard = system.GuardProxy(self.plane.guard(a["name"]),
                                       self.annotate)
        self.counts = cell_module(self.cell, "roofline")
        self.engine = system.build_engine(self.cfg, self.params, self.guard,
                                          self.counts)
        self.engine.warmup()
        lap("engine_warmup_s")
        self._warm_paths()
        lap("warm_paths_s")
        if self.cotenant is not None:
            self._settle_cotenant()
            lap("cotenant_settle_s")
        self.warm_counts = self.engine.compile_counts()
        self.timeline["compiles_in_setup"] = self.compiles.count

    # ------------------------------------------------------------------
    def _warm_paths(self) -> None:
        """``engine.warmup()`` compiles the step programs but not the small
        conversions its dispatch paths make on their first call
        (``jnp.asarray([start], int32)``, ``jnp.ones((1,), bool)``): two
        short requests take the prefill, mixed and decode paths once, so
        that nothing at all compiles inside the window."""
        import numpy as np

        from kubeshare_tpu.serving import Request

        engine = self.engine
        span = engine.engine_config.decode_span
        first = engine.submit(Request("warm-0", np.arange(1, 6, dtype=np.int32),
                                      3 * span))
        while first.first_token_at is None:
            engine.step()
        engine.submit(Request("warm-1", np.arange(7, 12, dtype=np.int32), 2))
        while engine.step():
            pass
        self.guard.finish()
        engine.pop_finished()

    def _settle_cotenant(self, longest_s: float = 20.0) -> None:
        """Pod B starts with an empty ledger and may burst until its decayed
        share reaches its limit; the window opens once it is there."""
        spec = self.mix["cotenant"]
        end = time.monotonic() + longest_s
        while time.monotonic() < end:
            share = self.plane.stat()[spec["name"]]["share"]
            if share >= 0.9 * spec["gpu_limit"]:
                return
            time.sleep(0.2)

    def _counters(self) -> Dict:
        e = self.engine
        return {"decode_steps": e.decode_steps,
                "prefill_chunks": e.prefill_chunks,
                "mixed_steps": e.mixed_steps,
                "tokens_generated": e.tokens_generated,
                "requests_admitted": e.requests_admitted,
                "requests_finished": e.requests_finished,
                "planner": e.host_planner_invocations,
                "host_seconds": dict(e.host_seconds),
                "acquire_calls": self.guard.acquire_calls,
                "acquire_wait_s": self.guard.acquire_wait_s,
                "tokens_acquired": self.guard.tokens_acquired,
                "gated_ms": self.guard.total_gated_ms}

    def measure(self, seconds: float, rate_rps: Optional[float] = None,
                trace: bool = False) -> Dict:
        """Drive the cell's traffic for ``seconds`` (and the mix's drain
        after it).  Returns the record the metrics are worked out from."""
        import jax

        from kubeshare_tpu.serving import Request

        engine, mix = self.engine, self.mix
        backlog = mix["arrivals"] == "backlog"
        # in the order they are due, without end; those due before
        # ``seconds`` are the window's own, the rest keep the load on
        # through the drain and are not scored
        source = traffic.requests(mix, rate_rps, seconds,
                                  self.tc["vocab_size"], self.seed)
        coming = next(source)
        depth = int(mix["queue_depth"]) if backlog else None
        drain = float(mix["drain_seconds"])
        trace_len = min(TRACE_SECONDS, seconds / 2.0) if trace else 0.0
        trace_dir = os.path.join(STATE_DIR, "trace")
        sent: Dict[str, Dict] = {}  # rid -> request, result, lateness
        steps: List[Dict] = []
        queue_at: Dict[str, int] = {}
        annotate = self.annotate
        temperature = float(mix.get("temperature", 0.0))
        tag = f"s{self.seed}-"

        def submit(req) -> None:
            result = engine.submit(Request(tag + req.rid, req.prompt,
                                           req.max_new, temperature))
            sent[req.rid] = {"request": req, "result": result,
                             "late_s": now - req.due,
                             "scored": req.due < seconds}

        def live_rows() -> List[int]:
            return [entry["result"].prompt_len + len(entry["result"].tokens)
                    for entry in sent.values()
                    if entry["result"].first_token_at is not None
                    and not entry["result"].done]

        def unstarted() -> int:
            return sum(1 for entry in sent.values()
                       if entry["result"].first_token_at is None)

        compiles_before = self.compiles.count
        stat0 = self.plane.stat()
        before = self._counters()
        tracing, traced, window_span = False, False, None
        closed, stat1, after = False, None, None
        watch = StallWatch()
        opened = time.monotonic()
        index = 0
        while True:
            now = time.monotonic() - opened
            if trace and not traced and not tracing \
                    and now >= seconds - trace_len:
                shutil.rmtree(trace_dir, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                window_span = annotate("chipbench.window")
                window_span.__enter__()
                tracing = True
            if not closed and now >= seconds:
                # the window closes: counters and tokend's ledger are read
                # here; what follows is the drain
                closed_at = now
                stat1, after = self.plane.stat(), self._counters()
                queue_at["end"] = engine.load_probe()["queue_depth"]
                queue_at["unstarted_end"] = unstarted()
                if tracing:
                    # writing the trace out takes seconds: they are taken
                    # out of the drain's clock, not out of the requests'
                    window_span.__exit__(None, None, None)
                    t_stop = time.monotonic()
                    jax.profiler.stop_trace()
                    drain += time.monotonic() - t_stop
                    tracing, traced = False, True
                closed = True
                if backlog:
                    break
            if "mid" not in queue_at and now >= seconds / 2.0:
                queue_at["mid"] = engine.load_probe()["queue_depth"]
                queue_at["unstarted_mid"] = unstarted()
            if closed and (now >= seconds + drain or all(
                    e["result"].done for e in sent.values() if e["scored"])):
                break
            while coming.due <= now and (
                    depth is None
                    or engine.load_probe()["queue_depth"] < depth):
                with annotate("chipbench.submit"):
                    submit(coming)
                coming = next(source)
            c0 = (engine.decode_steps, engine.prefill_chunks,
                  engine.mixed_steps)
            rows = live_rows() if trace else None
            watch.begin()
            with annotate("chipbench.engine.step", i=index):
                worked = engine.step()
            c1 = (engine.decode_steps, engine.prefill_chunks,
                  engine.mixed_steps)
            kind = None
            if c1 != c0:
                kind = ("mixed" if c1[2] > c0[2] else
                        "decode" if c1[0] > c0[0] else "prefill")
                steps.append({"i": index, "kind": kind, "rows": rows,
                              "in_window": not closed})
            watch.end(now, index, kind)
            index += 1
            if not worked:
                with annotate("chipbench.sleep-until-due"):
                    time.sleep(max(0.0, min(0.001, coming.due - now)))
        ended = time.monotonic() - opened
        watch.close()
        if engine.guard is not None:
            engine.guard.finish()
        record = {
            "seconds": closed_at, "ended_s": ended, "opened_at": opened,
            "sent": sent, "steps": steps, "queue_at": queue_at,
            "counters": _delta(after, before),
            "stat": {name: {k: stat1[name][k] - stat0[name][k]
                            for k in ("charged_total_ms", "grants")}
                     | {"limit": stat1[name]["limit"],
                        "request": stat1[name]["request"]}
                     for name in stat1},
            "compiles_in_window": self.compiles.count - compiles_before,
            "compile_counts_moved":
                self.engine.compile_counts() != self.warm_counts,
            "decode_span": engine.engine_config.decode_span,
            "trace": None, "backlog": backlog,
            "long_steps": watch.long_steps,
        }
        if self.cotenant is not None:
            spec = mix["cotenant"]
            record["cotenant_steps"] = self.cotenant.steps_between(
                opened, opened + closed_at)
            record["cotenant_step_flops"] = roofline.matmul_chain_flops(
                spec["matmul_n"], spec["chain"])
            for long in record["long_steps"]:
                at = opened + long["at_s"]
                long["cotenant_steps"] = self.cotenant.steps_between(
                    at, at + long["wall_s"])
        if traced:
            from chipbench.trace import find_xplane, reduce_trace

            t_reduce, xplane = time.monotonic(), find_xplane(trace_dir)
            record["trace"] = reduce_trace(xplane)
            say(trace_file_bytes=os.path.getsize(xplane),
                trace_reduce_s=round(time.monotonic() - t_reduce, 2))
        return record

    # ------------------------------------------------------------------
    def finished(self, record: Dict) -> List[Dict]:
        return [e for e in scored(record)
                if e["result"].done
                and len(e["result"].tokens) == e["request"].max_new]

    def sample(self, record: Dict) -> List[Dict]:
        """The requests the reference re-computes: drawn from the seed out
        of those the window finished, the longest always among them."""
        import numpy as np

        done = self.finished(record)
        if not done:
            return []
        size = lambda e: len(e["request"].prompt) + e["request"].max_new
        longest = max(done, key=size)
        rest = [e for e in done if e is not longest]
        rng = np.random.default_rng([int(self.seed), 7])
        picks = rng.permutation(len(rest))[
            :int(self.cfg["correct"]["sample_requests"]) - 1]
        return [longest] + [rest[i] for i in picks]

    def release_engine(self) -> None:
        """Frees the pool before the reference runs; the weights stay (the
        reference reads the same arrays)."""
        self.engine = None
        gc.collect()

    def close(self) -> None:
        try:
            if self.cotenant is not None:
                self.cotenant.stop()
        finally:
            self.plane.close()


# ---------------------------------------------------------------------------
# from a record to metrics and to ``correct``
# ---------------------------------------------------------------------------

def end_to_end(cell: Dict, record: Dict, setup_s: float) -> Dict:
    """Every end-to-end metric this record can give, and the sample counts
    that go on an earlier line."""
    window = record["seconds"]
    values: Dict[str, float] = {"setup_s": setup_s}
    notes: Dict = {}
    entries = scored(record)
    if record["backlog"]:
        values["tokens_per_s"] = \
            record["counters"]["tokens_generated"] / window
        notes["tokens_in_window"] = record["counters"]["tokens_generated"]
    else:
        ttft = [metrics.ttft_seconds(record["opened_at"], e["request"].due,
                                     e["result"].first_token_at, window)
                for e in entries]
        gaps = [g for g in (metrics.token_gap_seconds(
            e["result"].first_token_at, e["result"].finished_at,
            len(e["result"].tokens)) for e in entries) if g is not None]
        # every statistic is worked out; BENCHMARK.json says which of them
        # are the cell's end-to-end metrics, the others go on the notes line
        if ttft:
            values["ttft_p90_ms"] = metrics.percentile(ttft, 90) * 1e3
            values["ttft_tail_ms"] = metrics.slowest_tenth_mean(ttft) * 1e3
            notes["ttft_mean_ms"] = sum(ttft) / len(ttft) * 1e3
            notes["ttft_p50_ms"] = metrics.percentile(ttft, 50) * 1e3
            notes["generator_late_p95_ms"] = metrics.percentile(
                [e["late_s"] for e in entries], 95) * 1e3
        if gaps:
            values["token_gap_p90_ms"] = metrics.percentile(gaps, 90) * 1e3
            values["token_gap_mean_ms"] = sum(gaps) / len(gaps) * 1e3
            notes["token_gap_p50_ms"] = metrics.percentile(gaps, 50) * 1e3
        # the same percentiles over what happened inside the window only:
        # what the per-layer readers take, because in a --trace 1 run the
        # profiler's stop (some 20 s) falls into the drain
        closes = record["opened_at"] + window
        early = [t for t, e in zip(ttft, entries)
                 if e["result"].first_token_at is not None
                 and e["result"].first_token_at <= closes]
        early_gaps = [g for g, e in (
            (metrics.token_gap_seconds(e["result"].first_token_at,
                                       e["result"].finished_at,
                                       len(e["result"].tokens)), e)
            for e in entries) if g is not None
            and e["result"].finished_at <= closes]
        if early:
            notes["ttft_p90_in_window_ms"] = \
                metrics.percentile(early, 90) * 1e3
        if early_gaps:
            notes["token_gap_p90_in_window_ms"] = \
                metrics.percentile(early_gaps, 90) * 1e3
        notes["ttft_samples"], notes["gap_samples"] = len(ttft), len(gaps)
        # every request's time, in the order they were due: whatever is
        # asked of a run later can be worked out again from its line
        notes["ttft_ms_by_due"] = [round(t * 1e3, 1) for t in ttft]
        notes["gap_ms_by_due"] = [round(g * 1e3, 2) for g in gaps]
    if "cotenant_steps" in record:
        values["cotenant_tflops"] = (record["cotenant_steps"]
                                     * record["cotenant_step_flops"]
                                     / window / 1e12)
        notes["cotenant_steps"] = record["cotenant_steps"]
    # sent and not admitted, and sent and still without a first token, at
    # the window's midpoint and end: a rate is sustained when neither grows
    q = record["queue_at"]
    notes["queue_depth_mid_end"] = [q.get("mid"), q.get("end")]
    notes["unstarted_mid_end"] = [q.get("unstarted_mid"),
                                  q.get("unstarted_end")]
    wanted = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    out = {name: {"value": values[name], "unit": unit}
           for name, unit in wanted.items() if name in values}
    notes.update({k: v for k, v in values.items() if k not in wanted})
    return {"metrics": out, "notes": notes}


def load_reader(directory: str, name: str):
    """The reader of one per-layer metric: the file named after it."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(directory, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def per_layer(cell: Dict, run: Dict) -> Dict:
    """Each per-layer metric of the cell, by the reader named after it."""
    out = {}
    for metric in cell["per_layer"]:
        value = load_reader(cell["metric_dir"], metric["name"]).read(run)
        if value is not None:  # a reader that finds nothing returns nothing
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def judge(session: Session, record: Dict) -> Dict:
    """``correct``: every request served in full, the served tokens within
    the configuration's limits of the plain reference, and the token
    runtime's guarantees.  Prints each number beside its limit."""
    cell, mix = session.cell, session.mix
    checks: List[Dict] = []

    def check(name: str, value, limit, ok: bool) -> None:
        checks.append({"check": name, "value": value, "limit": limit,
                       "ok": bool(ok)})

    entries = scored(record)
    done = session.finished(record)
    if record["backlog"]:
        attempted = record["counters"]["requests_admitted"]
        failed = 0  # an errored request raises out of the engine
        check("requests_finished_in_window", len(done), ">= 1", len(done) >= 1)
    else:
        attempted = len(entries)
        failed = attempted - len(done)
        check("requests_not_served_in_full", failed, 0, failed == 0)

    a = session.cfg["pod"]["name"]
    stat = record["stat"]
    check("pod_a_granted_and_charged",
          [stat[a]["grants"], stat[a]["charged_total_ms"]], "> 0",
          stat[a]["grants"] > 0 and stat[a]["charged_total_ms"] > 0
          and record["counters"]["tokens_acquired"] > 0)
    if mix.get("cotenant"):
        b = mix["cotenant"]
        share = stat[b["name"]]["charged_total_ms"] / (record["seconds"] * 1e3)
        check("pod_b_granted_and_charged", stat[b["name"]]["grants"], "> 0",
              stat[b["name"]]["grants"] > 0 and share > 0)
        cap = b["gpu_limit"] + b["limit_tolerance"]
        check("pod_b_share_of_window", share, cap, share <= cap)

    # the plain reference, after the engine's pool is freed
    reference = cell_module(cell, "reference")
    limits = session.cfg["correct"]
    session.release_engine()
    t0 = time.monotonic()
    sample = session.sample(record)
    gaps = [reference.served_gaps(session.params, session.tc,
                                  e["request"].prompt, e["result"].tokens)
            for e in sample]
    # the numbers compared are the ones the configuration states a limit
    # for: "<name>_limit" in its correct block, <name> in what its
    # reference's summarize() returns
    compared = {key[:-len("_limit")]: limit for key, limit in limits.items()
                if key.endswith("_limit")}
    widest = compared.get("widest_gap", float("inf"))
    for e, g in zip(sample, gaps):
        # request by request, so that a run that fails says where
        far = [int(i) for i in (g > widest).nonzero()[0]]
        say(reference_request={
            "rid": e["request"].rid, "prompt": len(e["request"].prompt),
            "served": len(g), "widest_gap": float(g.max()),
            "mean_gap": float(g.mean()), "over_limit": len(far),
            "over_limit_at": far[:12]})
    if gaps and compared:
        summary = reference.summarize(gaps)
        for name, limit in compared.items():
            check(f"served_vs_reference.{name}", summary[name], limit,
                  summary[name] <= limit)
        summary["reference_s"] = round(time.monotonic() - t0, 2)
        summary["requests"] = len(gaps)
        say(reference=summary)
    else:
        check("served_vs_reference.sample", len(gaps),
              ">= 1 request, >= 1 limit", False)
    for c in checks:
        say(**c)
    return {"correct": all(c["ok"] for c in checks), "attempted": attempted,
            "failed": failed, "checks": checks}


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, inventory=None) -> Dict:
    import jax

    session = Session(cell, seed, require_tpu=require_tpu,
                      inventory=inventory)
    try:
        setup_s = process_age_s()
        say(setup=session.timeline, setup_s=setup_s,
            compile_cache=session.cache_dir, pods=session.plane.pods,
            pool={"num_blocks": session.engine.engine_config.num_blocks,
                  "bytes": system.pool_bytes(session.engine.pool)},
            share_table=session.plane.share_table)
        record = session.measure(seconds, cell["params"].get("rate_rps"),
                                 trace=trace)
        if record["compiles_in_window"] or record["compile_counts_moved"]:
            raise SystemExit(
                f"chipbench: {record['compiles_in_window']} program(s) "
                f"compiled inside the measured window")
        stats = jax.local_devices()[0].memory_stats() or {}
        device = dict(session.device,
                      memory_peak_bytes=max(
                          (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in jax.local_devices()))
        e2e = end_to_end(cell, record, setup_s)
        say(window_s=record["seconds"], ended_s=record["ended_s"],
            counters=record["counters"], stat=record["stat"],
            bytes_limit=stats.get("bytes_limit"),
            long_steps=record["long_steps"], **e2e["notes"])
        result = {"metrics": e2e["metrics"], "device": device}
        if trace:
            summary = record["trace"]
            run = {"cell": cell, "record": record, "trace": summary,
                   "tc": session.tc, "roofline": session.counts,
                   "device_kind": session.device["kind"],
                   "pod_a": session.cfg["pod"]["name"],
                   "notes": e2e["notes"]}
            result["metrics"] = per_layer(cell, run)
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = {"device_ops": summary.device_ops,
                                   "idle_gaps": summary.idle_gaps}
        verdict = judge(session, record)
        # each number compared beside its limit: the last lines on standard
        # error, and the result line's last key
        for c in verdict["checks"]:
            print(f"chipbench: {c['check']} = {c['value']} (limit "
                  f"{c['limit']}): {'ok' if c['ok'] else 'NOT OK'}",
                  file=sys.stderr, flush=True)
        return {"correct": verdict["correct"],
                "attempted": verdict["attempted"],
                "failed": verdict["failed"], **result,
                "checks": {c["check"]: {"value": c["value"],
                                        "limit": c["limit"]}
                           for c in verdict["checks"]}}
    finally:
        session.close()


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    cell = load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
