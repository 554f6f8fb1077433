"""The program's spans and counters (utils/profiling.py) at the layer
boundaries of the serving path: engine -> guard -> token client.

A tiny engine on the CPU under a real ``ExecutionGuard`` over a fake token
client.  What is locked: the span mechanism itself, that the engine's
``host_seconds`` are fed by its phase spans, that the parts of a dispatch fit
inside it, the request's lifecycle stamps, the guard's counters, the slow-
dispatch line, that naming the step programs costs no recompile, and the
table of stages: the parser, the registry ``warmup()`` fills, that serving
lowers nothing for it, and the file ``profile_trace`` leaves.
"""

import glob
import json
import logging
import re
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.isolation import ExecutionGuard
from kubeshare_tpu.models.transformer import TransformerConfig, transformer_init
from kubeshare_tpu.serving import (EngineConfig, Request, ServingEngine,
                                   plan_prefill_chunks)
from kubeshare_tpu.serving import engine as engine_module
from kubeshare_tpu.serving import stages
from kubeshare_tpu.utils import profiling

pytestmark = pytest.mark.serving

PHASES = ("admit", "consume", "tune", "plan", "dispatch")
# one a lowering of a jitted function to a module (jax._src.interpreters.mlir)
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
QUOTA_MS = 40.0


class FakeTokenClient:
    """Grants at once; counts what it was asked."""

    pod_name = "default/serve-a"

    def __init__(self) -> None:
        self.acquired = 0
        self.released = []

    def acquire(self, est_ms: float = 0.0) -> float:
        self.acquired += 1
        return QUOTA_MS

    def release(self, used_ms: float) -> None:
        self.released.append(used_ms)


@pytest.fixture(scope="module")
def model():
    config = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32, attention="reference")
    return config, transformer_init(jax.random.PRNGKey(0), config)


def _engine(model, guard=None, **overrides):
    config, params = model
    kwargs = dict(num_slots=3, block_size=4, num_blocks=41,
                  max_request_len=48, prefill_chunk=8)
    kwargs.update(overrides)
    return ServingEngine(params, config, EngineConfig(**kwargs), guard=guard)


def _guard():
    # no idle monitor: nothing but the test's own thread touches the guard
    return ExecutionGuard(client=FakeTokenClient(), from_env=False,
                          idle_release_ms=0)


@pytest.fixture(scope="module")
def served(model):
    """One guarded engine, warmed, that served two requests; the spans of
    that run, and the engine as it was left."""
    guard = _guard()
    engine = _engine(model, guard)
    registry, stages._programs = stages._programs, {}  # this engine's alone
    lowerings = _Lowerings()
    try:
        engine.warmup()
        assert lowerings.count > 0  # the counter counts: warm-up lowers
        lowerings.count = 0
        warm = engine.compile_counts()
        before = dict(engine.host_seconds)
        since = time.monotonic()
        long = engine.submit(
            Request("long", np.arange(1, 20, dtype=np.int32), 8))
        short = engine.submit(
            Request("short", np.arange(3, 9, dtype=np.int32), 5))
        engine.run()
        mine = [r for r in profiling.spans(since=since)
                if r[3] == threading.current_thread().name]
        return {"engine": engine, "guard": guard, "warm": warm,
                "before": before, "spans": mine, "long": long,
                "short": short, "registered": sorted(stages._programs),
                "tables_built": [n for n, p in stages._programs.items()
                                 if p.table is not None],
                "lowerings_in_window": lowerings.count}
    finally:
        lowerings.counting = False
        stages._programs = registry


class _Lowerings:
    """Counts the lowerings of jitted functions while ``counting``, by
    JAX's own monitoring events (a listener cannot be taken off again)."""

    def __init__(self) -> None:
        self.count, self.counting = 0, True
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_) -> None:
        if self.counting and event == LOWERING_EVENT:
            self.count += 1


def _named(records, name):
    return [r for r in records if r[0] == "kubeshare." + name]


def _seconds(records, name):
    return sum(r[2] - r[1] for r in _named(records, name))


# -- the mechanism ----------------------------------------------------------

def test_spans_nest_and_carry_their_attributes():
    since = time.monotonic()
    with profiling.span("test.outer", i=7) as outer:
        with profiling.span("test.inner", kind="mixed") as inner:
            inner.set(lanes=3)
    got = {r[0]: r for r in profiling.spans(since=since)
           if r[0].startswith("test.")}
    assert set(got) == {"test.outer", "test.inner"}
    o, i = got["test.outer"], got["test.inner"]
    assert o[1] <= i[1] <= i[2] <= o[2]
    assert o[4] == {"i": 7} and i[4] == {"kind": "mixed", "lanes": 3}
    assert o[3] == i[3] == threading.current_thread().name
    assert outer.seconds == o[2] - o[1] >= inner.seconds > 0
    assert profiling.spans(since=since, name="test.inner") == [i]
    assert profiling.spans(since=time.monotonic(), name="test.inner") == []


def test_profiling_and_the_guard_import_without_jax():
    code = ("import sys; import kubeshare_tpu.utils.profiling as p; "
            "import kubeshare_tpu.isolation.guard; "
            "s = p.span('x'); s.__enter__(); s.__exit__(None, None, None); "
            "assert p.spans(name='x'); "
            "assert not hasattr(p, 'timed'); "
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_the_ring_is_bounded():
    for i in range(profiling._RING_SIZE + 50):
        with profiling.span("test.flood", i=i):
            pass
    kept = profiling.spans()
    assert len(kept) == profiling._RING_SIZE
    assert kept[-1][4] == {"i": profiling._RING_SIZE + 49}


def test_spans_land_in_a_profiler_trace(model, tmp_path):
    """While a profiler session runs, the same spans are host events of the
    ``.xplane.pb`` with their attributes as stats."""
    engine = _engine(model, _guard())
    engine.warmup()
    with profiling.profile_trace(str(tmp_path)):
        engine.submit(Request("r", np.arange(1, 12, dtype=np.int32), 3))
        engine.run()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("kubeshare."):
                    events.setdefault(e.name, []).append(dict(e.stats))
    assert set(events) >= {"kubeshare." + n for n in (
        "engine.admit", "engine.consume", "engine.plan",
        "engine.dispatch", "engine.marshal", "engine.launch",
        "engine.device_wait", "engine.fetch", "guard.acquire",
        "guard.gated")}
    first = events["kubeshare.engine.launch"][0]
    assert first["kind"] == "prefill" and first["chunk"] == 8
    assert first["program"] == "prefill/8"
    # what the admit phase saw goes with it into the trace
    admits = events["kubeshare.engine.admit"]
    assert admits[0]["queued"] == 1 and admits[0]["admitted"] == 1
    assert sum(a["admitted"] for a in admits) == 1
    assert {a["matched_rows"] for a in admits} == {0}
    assert {s["broker"] for s in events["kubeshare.guard.acquire"]} <= {0, 1}
    assert all(s["pod"] == "default/serve-a"
               for s in events["kubeshare.guard.gated"])


# -- the engine's phases ----------------------------------------------------

def test_host_seconds_keep_five_keys_fed_by_the_phase_spans(served):
    engine, records = served["engine"], served["spans"]
    assert set(engine.host_seconds) == set(PHASES)
    for phase in PHASES:
        moved = engine.host_seconds[phase] - served["before"][phase]
        assert moved == pytest.approx(_seconds(records, "engine." + phase),
                                      rel=1e-9, abs=1e-12), phase
    assert engine.host_seconds["tune"] == 0.0  # no tuner: no span either
    assert not _named(records, "engine.tune")
    # the phases of a step follow one another: admit, consume, plan and,
    # where there was a plan, dispatch; nothing holds them (no reader read
    # the step's own span, so it went)
    assert not _named(records, "engine.step")
    phases = sorted((r for p in PHASES
                     for r in _named(records, "engine." + p)),
                    key=lambda r: r[1])
    assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
    initials = "".join(r[0].rsplit(".", 1)[1][0] for r in phases)
    assert re.fullmatch("(acpd?)+", initials), initials


def test_the_admit_span_says_what_the_call_saw(served):
    """``queued`` on entry, ``admitted`` and ``matched_rows`` by the call:
    what a longer admit phase cannot be explained without."""
    engine, admits = served["engine"], _named(served["spans"], "engine.admit")
    assert [a[4]["queued"] for a in admits[:2]] == [2, 0]
    assert sum(a[4]["admitted"] for a in admits) == 2 \
        == engine.requests_admitted
    assert sum(a[4]["matched_rows"] for a in admits) \
        == engine.prefix_hit_tokens
    assert all(set(a[4]) == {"queued", "admitted", "matched_rows"}
               for a in admits)


def test_the_parts_of_a_dispatch_fit_inside_it(served):
    records = served["spans"]
    dispatches = _named(records, "engine.dispatch")
    launches = _named(records, "engine.launch")
    assert len(dispatches) == len(launches) == \
        len(_named(records, "engine.marshal")) == \
        len(_named(records, "engine.device_wait")) == \
        len(_named(records, "guard.gated"))
    parts = ("engine.marshal", "guard.acquire", "engine.launch",
             "engine.device_wait")
    for d in dispatches:
        inside = [r for r in records if r[0][len("kubeshare."):] in parts
                  and d[1] <= r[1] and r[2] <= d[2]]
        assert sorted(r[0][len("kubeshare."):] for r in inside) == \
            sorted(parts)
        assert sum(r[2] - r[1] for r in inside) <= d[2] - d[1]
    assert sum(_seconds(records, p) for p in parts) <= \
        _seconds(records, "engine.dispatch")
    # the launch says what the plan carried
    kinds = [r[4]["kind"] for r in launches]
    assert kinds[0] == "prefill" and set(kinds) <= {"prefill", "mixed",
                                                    "decode"}
    assert all(r[4]["chunk"] > 0 for r in launches
               if r[4]["kind"] in ("prefill", "mixed"))
    assert all(r[4]["lanes"] >= 1 and r[4]["rows"] >= r[4]["lanes"]
               for r in launches if r[4]["kind"] in ("mixed", "decode"))
    # the gated interval covers the launch and the wait on the device
    for g, l, w in zip(_named(records, "guard.gated"), launches,
                       _named(records, "engine.device_wait")):
        assert g[1] <= l[1] and w[2] <= g[2] and g[4]["pod"] == \
            "default/serve-a"
    # the fetch is a part of consume
    assert 0 < _seconds(records, "engine.fetch") <= \
        _seconds(records, "engine.consume")


def test_an_unguarded_dispatch_has_no_device_wait(model):
    engine = _engine(model)
    engine.warmup()
    since = time.monotonic()
    engine.submit(Request("r", np.arange(1, 10, dtype=np.int32), 3))
    engine.run()
    names = {r[0] for r in profiling.spans(since=since)
             if r[3] == threading.current_thread().name}
    assert "kubeshare.engine.launch" in names
    assert "kubeshare.engine.marshal" in names
    assert not names & {"kubeshare.engine.device_wait",
                        "kubeshare.guard.acquire", "kubeshare.guard.gated"}


# -- the request's stamps ---------------------------------------------------

@pytest.mark.parametrize("rid", ["long", "short"])
def test_request_stamps_are_ordered_and_chunks_counted(served, rid):
    result = served[rid]
    assert result.done
    assert result.submitted_at <= result.admitted_at \
        <= result.first_dispatch_at <= result.first_token_at \
        <= result.finished_at
    chunks, _ = plan_prefill_chunks(result.prompt_len, 8, max_len=48)
    assert result.prefill_chunks == len(chunks)
    # the stamp is the launch of a dispatch that carried a chunk
    launches = _named(served["spans"], "engine.launch")
    assert result.first_dispatch_at in [r[1] for r in launches
                                        if r[4]["chunk"] > 0]


# -- the guard --------------------------------------------------------------

def test_guard_counts_held_and_broker_acquires():
    guard = _guard()
    since = time.monotonic()
    assert guard.acquire() == QUOTA_MS  # nothing held: the broker is asked
    guard.charge(1.0)
    guard.acquire()  # 39 ms of budget against a 1 ms estimate: held
    guard.charge(1.0)
    assert (guard.acquire_calls, guard.broker_calls) == (2, 1)
    assert guard.client.acquired == guard.tokens_acquired == 1
    assert 0 < guard.broker_wait_s < guard.acquire_wait_s
    guard.acquire()
    guard.charge(QUOTA_MS)  # spends the token: it is returned
    guard.acquire()
    guard.charge(1.0)
    assert (guard.acquire_calls, guard.broker_calls) == (4, 2)
    acquires = [r for r in profiling.spans(since=since)
                if r[0] == "kubeshare.guard.acquire"]
    assert [r[4]["broker"] for r in acquires] == [1, 0, 0, 1]
    assert sum(r[2] - r[1] for r in acquires) == \
        pytest.approx(guard.acquire_wait_s, rel=1e-9)
    gated = profiling.spans(since=since, name="kubeshare.guard.gated")
    assert len(gated) == 4
    assert all(a[2] <= g[1] for a, g in zip(acquires, gated))
    assert ExecutionGuard(client=None, from_env=False).acquire() == 0.0


def test_token_client_span_counts_its_round_trips():
    """A broker that says WAIT once, then grants: one span, two trips,
    nested inside the guard's acquire span."""
    import socket

    from kubeshare_tpu.isolation import TokenClient

    server = socket.create_server(("127.0.0.1", 0))
    replies = iter(("WAIT 1\n", "TOK 40.0\n", "OK\n"))

    def broker():
        conn, _ = server.accept()
        with conn, conn.makefile("rw", newline="\n") as f:
            for reply in replies:
                if not f.readline():
                    return
                f.write(reply)
                f.flush()

    thread = threading.Thread(target=broker, daemon=True)
    thread.start()
    client = TokenClient("127.0.0.1", server.getsockname()[1], "ns/pod-x")
    guard = ExecutionGuard(client=client, from_env=False, idle_release_ms=0)
    since = time.monotonic()
    try:
        assert guard.acquire() == 40.0
        guard.charge(40.0)  # spends the token: RET goes out
    finally:
        client.close()
        server.close()
    thread.join(timeout=10)
    assert not thread.is_alive()
    asked, = profiling.spans(since=since, name="kubeshare.client.acquire")
    assert asked[4] == {"round_trips": 2}  # the guard's span has the pod
    outer, = profiling.spans(since=since, name="kubeshare.guard.acquire")
    assert outer[4] == {"pod": "ns/pod-x", "broker": 1}
    assert outer[1] <= asked[1] and asked[2] <= outer[2]
    assert guard.broker_wait_s >= asked[2] - asked[1] > 0


def test_guard_counters_are_on_the_metrics_plane(served):
    engine, guard = served["engine"], served["guard"]
    families = {f.name: f for f in engine.collect_metrics()}
    calls = {s.labels["kind"]: s.value for s in
             families["kubeshare_serving_guard_calls_total"].samples}
    waits = {s.labels["kind"]: s.value for s in
             families["kubeshare_serving_guard_wait_seconds_total"].samples}
    assert calls == {"held": guard.acquire_calls - guard.broker_calls,
                     "broker": guard.broker_calls}
    assert calls["held"] + calls["broker"] == \
        len(_named(served["spans"], "guard.acquire"))
    assert waits["held"] + waits["broker"] == \
        pytest.approx(guard.acquire_wait_s)
    slow = families["kubeshare_serving_slow_dispatches_total"].samples
    assert {s.labels["phase"] for s in slow} == {"acquire", "launch",
                                                 "device_wait"}
    assert sum(s.value for s in slow) == 0
    # an engine without a guard exports the families empty
    bare = {f.name: f for f in _bare_metrics(served)}
    assert not bare["kubeshare_serving_guard_calls_total"].samples


def _bare_metrics(served):
    engine = served["engine"]
    guard, engine.guard = engine.guard, None
    try:
        return engine.collect_metrics()
    finally:
        engine.guard = guard


# -- the slow dispatch ------------------------------------------------------

def test_a_slow_dispatch_is_named_once(model, monkeypatch):
    engine = _engine(model, _guard())
    engine.warmup()
    engine.submit(Request("warm", np.arange(1, 10, dtype=np.int32), 3))
    engine.run()  # the running estimate settles at this machine's speed
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record)
    engine.log.addHandler(handler)
    monkeypatch.setattr(engine_module, "SLOW_DISPATCH_S", 0.05)
    fast = engine._prefill_step
    slow_for = max(0.2, 10 * engine._dispatch_estimate_ms / 1e3)

    def slow(*args):
        time.sleep(slow_for)
        return fast(*args)

    try:
        engine.submit(Request("r", np.arange(2, 12, dtype=np.int32), 3))
        engine._prefill_step = slow
        engine.step()
        engine._prefill_step = fast
        engine.run()
    finally:
        engine.log.removeHandler(handler)
    warnings = [r for r in lines if r.levelno == logging.WARNING]
    assert len(warnings) == 1
    text = warnings[0].getMessage()
    assert "slow dispatch" in text and "kind=prefill" in text
    assert "program=prefill/8" in text and "chunk=8" in text
    assert "experts=none" in text  # a dense engine: no expert layer
    # the fake client makes no client span: a real one adds its round trips
    assert "(broker)" in text or "(held)" in text
    assert engine.slow_dispatches == {"acquire": 0, "launch": 1,
                                      "device_wait": 0}


# -- the named programs -----------------------------------------------------

def test_zero_recompiles_after_warmup_with_the_named_programs(served):
    engine = served["engine"]
    assert engine.compile_counts() == served["warm"]
    for kind in ("prefill", "decode", "mixed", "verify", "mixed_verify",
                 "copy", "upload"):
        step = getattr(engine, f"_{kind}_step")
        assert step.__name__ == f"kubeshare_{kind}_step"
    lowered = engine._copy_step.lower(
        engine.pool.k, engine.pool.v, jnp.zeros((), jnp.int32),
        jnp.zeros((), jnp.int32))
    assert "jit_kubeshare_copy_step" in lowered.as_text()[:400]


# -- the table of stages ----------------------------------------------------

def test_the_parser_gives_each_instruction_its_innermost_stage():
    """A small jitted function with nested scopes, a ``fori_loop`` and a
    fusion: the innermost recognised scope wins, a scope the vocabulary
    does not know is no stage, and what runs under none is ``unscoped``."""
    def f(x, w):
        with jax.named_scope("attention"):
            y = jnp.tanh(x @ w)  # the dot, and a fusion whose root is tanh
            with jax.named_scope("kv_write"):
                y = y.at[0].set(1.0)
            with jax.named_scope("not_a_stage"):
                y = y * 2.0
        with jax.named_scope("experts"):
            y = jax.lax.fori_loop(0, 3, lambda i, c: jnp.sin(c @ w), y)
        return jnp.cos(y) + 1.0

    x = jnp.ones((8, 8), jnp.float32)
    text = jax.jit(f).lower(x, x).compile().as_text()
    table = stages.instruction_stages(text)
    lines = {name: line for line in text.splitlines()
             for found in [stages._INSTRUCTION.match(line)] if found
             for name in [found.group(2)]}
    assert set(table) == set(lines)
    assert set(table.values()) == {"attention", "kv_write", "experts",
                                   "unscoped"}
    for name, line in lines.items():
        op_name = stages._OP_NAME.search(line)
        if op_name is None or not op_name.group(1).startswith("jit("):
            continue  # the compiler's own: takes its user's or caller's
        scopes = [s for s in op_name.group(1).split("/")
                  if s in stages.STAGE_OF_SCOPE]
        assert table[name] == (stages.STAGE_OF_SCOPE[scopes[-1]]
                               if scopes else "unscoped"), line
    # the loop and what its body holds are the experts'; the write inside
    # attention is the write's; the unknown scope falls to the one around it
    loops = [n for n, l in lines.items() if " while(" in l]
    assert loops and all(table[n] == "experts" for n in loops)
    in_loop = [n for n, l in lines.items() if "/experts/while/body" in l]
    assert in_loop and all(table[n] == "experts" for n in in_loop)
    assert any(table[n] == "kv_write" for n, l in lines.items()
               if "attention/kv_write" in l)
    assert all(table[n] == "attention" for n, l in lines.items()
               if "attention/not_a_stage" in l)
    assert any(table[n] == "unscoped" and "cos" in l
               for n, l in lines.items())
    fusions = [n for n, l in lines.items() if " fusion(" in l]
    assert fusions and {table[n] for n in fusions} <= set(stages.STAGES)
    assert stages.stage_of("jit(f)/mla/kv_write/scatter") == "kv_write"
    assert stages.stage_of("jit(f)/router/top_k") == "experts"
    assert stages.stage_of("w['layers'][0]['attn']['wq']") == "unscoped"


HLO_OF_THE_COMPILER = """
HloModule jit_f

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %carried = f32[4] get-tuple-element(%p), index=1
  %used = f32[4] fusion(%carried), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/while/body/mlp/mul"}
  %row = f32[1] fusion(%carried), kind=kLoop, calls=%fused.1
  %update = f32[4] dynamic-update-slice(%carried, %row)
  %next = f32[4] slice-start(%update)
  ROOT %result = (s32[], /*index=1*/f32[4]) tuple(%i, %next)
}

ENTRY %main (w: f32[4]) -> f32[4] {
  %w = f32[4] parameter(0), metadata={op_name="w"}
  %prefetch = f32[4] slice-start(%w)
  %done = f32[4] slice-done(%prefetch)
  %copy.1 = f32[4] copy(%w), metadata={op_name="w"}
  %loose = f32[4] copy(%w)
  %entered = (s32[], f32[4]) tuple(%zero, %done)
  %while.1 = (s32[], f32[4]) while(%entered), condition=%cond, body=%body, metadata={op_name="jit(f)/kv_write/scatter"}
  ROOT %out = f32[4] fusion(%copy.1), kind=kLoop, calls=%fused.2, metadata={op_name="jit(f)/lm_head/dot_general"}
}
"""


def test_what_the_compiler_made_takes_its_users_or_its_callers_stage():
    """No ``op_name``, or an argument's name where a scope path would be:
    a prefetch takes the stage of what reads it — through the tuple that
    enters a loop, and through a body's result into the next iteration —
    and the loop of updates a scatter became takes the ``while``'s."""
    table = stages.instruction_stages(HLO_OF_THE_COMPILER)
    assert table["prefetch"] == table["done"] == "ffn"  # into the loop
    assert table["next"] == "ffn"  # made for the next iteration
    assert table["copy.1"] == "head"  # the head's own copy of a weight
    assert table["row"] == table["update"] == "ffn"  # first use with one
    assert table["while.1"] == "kv_write" and table["used"] == "ffn"
    assert table["loose"] == "unscoped"  # nothing uses it, nothing calls
    assert table["w"] == "ffn"  # a parameter's first reader
    only_updates = HLO_OF_THE_COMPILER.replace(
        ', metadata={op_name="jit(f)/while/body/mlp/mul"}', "")
    table = stages.instruction_stages(only_updates)
    assert table["update"] == table["row"] == table["used"] == "kv_write"


def test_warmup_registers_every_program_and_every_launch_names_one(served):
    """``compile_counts()`` lists so many programs a kind; so many names of
    that kind are in the registry, with shapes and no device array; and
    every launch of the served window names one of them."""
    engine = served["engine"]
    by_kind = {}
    for name in served["registered"]:
        kind, _, width = name.partition("/")
        by_kind.setdefault(kind, []).append(width)
    assert {k: len(v) for k, v in by_kind.items()} == \
        {k: n for k, n in served["warm"].items() if n}
    assert sorted(by_kind["prefill"], key=int) == \
        [str(w) for w in sorted(engine._warmed_widths)]
    launches = _named(served["spans"], "engine.launch")
    assert launches and {r[4]["program"] for r in launches} \
        <= set(served["registered"])
    assert {r[4]["program"].split("/")[0] == r[4]["kind"]
            for r in launches} == {True}
    assert {r[4]["program"] for r in launches if r[4]["kind"] == "decode"} \
        == {"decode/0"}
    assert all(r[4]["program"] == f"{r[4]['kind']}/{r[4]['chunk']}"
               for r in launches)
    assert not any("reach" in r[4] for r in launches)


def test_serving_a_window_builds_no_table_and_lowers_nothing(served):
    assert served["tables_built"] == []
    assert served["lowerings_in_window"] == 0


def test_a_table_is_built_on_demand_from_shapes_alone(model):
    """After the engine is gone: the registry holds the jitted function and
    ``ShapeDtypeStruct``s, one lowering builds the table, a second call
    builds nothing, and a name nothing registered has none."""
    import gc

    engine = _engine(model)
    engine.warmup()
    engine = None
    gc.collect()
    program = stages._programs["mixed/8"]
    leaves = jax.tree.leaves(program.avals)
    assert leaves and all(isinstance(a, jax.ShapeDtypeStruct)
                          for a in leaves)
    program.table = None
    lowerings = _Lowerings()
    try:
        table = stages.stage_table("mixed/8")
        built = lowerings.count
        assert stages.stage_table("mixed/8") is table
        assert lowerings.count == built <= 1  # 0: the tracing cache had it
    finally:
        lowerings.counting = False
    assert program.table is table
    assert {"attention", "kv_write", "ffn", "head"} <= set(table.values())
    assert stages.stage_table("mixed/7") is None


def test_profile_trace_leaves_the_tables_of_the_programs_it_saw(model,
                                                                tmp_path):
    engine = _engine(model, _guard())
    engine.warmup()
    with profiling.profile_trace(str(tmp_path)):
        since = time.monotonic()
        engine.submit(Request("r", np.arange(1, 12, dtype=np.int32), 3))
        engine.run()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / profiling.STAGES_FILE))
    assert glob.glob(path.replace(profiling.STAGES_FILE, "*.xplane.pb"))
    with open(path) as f:
        tables = json.load(f)["programs"]
    launched = {r[4]["program"] for r in profiling.spans(
        since=since, name="kubeshare.engine.launch")}
    assert set(tables) == launched >= {"prefill/8", "decode/0"}
    for name, table in tables.items():
        assert table == stages.stage_table(name)
        assert set(table.values()) <= set(stages.STAGES)
    # no session, no file; and a session that launched nothing leaves none
    with profiling.profile_trace(None):
        pass
    empty = tmp_path / "empty"
    with profiling.profile_trace(str(empty)):
        pass
    assert not glob.glob(str(empty / "**" / profiling.STAGES_FILE),
                         recursive=True)
