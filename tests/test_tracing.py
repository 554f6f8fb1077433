"""The program's spans and counters (utils/profiling.py) at the layer
boundaries of the serving path: engine -> guard -> token client.

A tiny engine on the CPU under a real ``ExecutionGuard`` over a fake token
client.  What is locked: the span mechanism itself, that the engine's
``host_seconds`` are fed by its phase spans, that the parts of a dispatch fit
inside it, the request's lifecycle stamps, the guard's counters, the slow-
dispatch line, and that naming the step programs costs no recompile.
"""

import glob
import logging
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
from kubeshare_tpu.utils import profiling

pytestmark = pytest.mark.serving

PHASES = ("admit", "consume", "tune", "plan", "dispatch")
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
    engine.warmup()
    warm = engine.compile_counts()
    before = dict(engine.host_seconds)
    since = time.monotonic()
    long = engine.submit(Request("long", np.arange(1, 20, dtype=np.int32), 8))
    short = engine.submit(Request("short", np.arange(3, 9, dtype=np.int32), 5))
    engine.run()
    mine = [r for r in profiling.spans(since=since)
            if r[3] == threading.current_thread().name]
    return {"engine": engine, "guard": guard, "warm": warm, "before": before,
            "spans": mine, "long": long, "short": short}


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
        "engine.step", "engine.admit", "engine.consume", "engine.plan",
        "engine.dispatch", "engine.marshal", "engine.launch",
        "engine.device_wait", "engine.fetch", "guard.acquire",
        "guard.gated")}
    assert [s["i"] for s in events["kubeshare.engine.step"]] == \
        sorted(s["i"] for s in events["kubeshare.engine.step"])
    first = events["kubeshare.engine.launch"][0]
    assert first["kind"] == "prefill" and first["chunk"] == 8
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
    steps = _named(records, "engine.step")
    assert [r[4]["i"] for r in steps] == list(range(
        steps[0][4]["i"], steps[0][4]["i"] + len(steps)))
    # every phase lies inside a step
    for phase in PHASES:
        for r in _named(records, "engine." + phase):
            assert any(s[1] <= r[1] and r[2] <= s[2] for s in steps), phase


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
    assert asked[4] == {"pod": "ns/pod-x", "round_trips": 2}
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
    assert "chunk=8" in text and ("(broker)" in text or "(held)" in text)
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
