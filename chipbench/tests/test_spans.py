"""The readers of the program's own spans (``layer_metrics/_spans.py``), on
the CPU, by hand (``pytest chipbench/tests``; not tier-1).

``data/tiny_spans.xplane.pb`` is the tiny rehearsal configuration on a TPU
v5e with the program's spans in it (``tools/record_fixture.py``, builder's
chip run, PR 24); ``data/tiny.xplane.pb`` is PR 23's, recorded before the
program had any: what the parent of PR 24 gives these readers.
"""

import json
import os
import shutil
from types import SimpleNamespace

import pytest

from chipbench import run
from chipbench.layer_metrics import _spans

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
WITH_SPANS = os.path.join(HERE, "data", "tiny_spans.xplane.pb")
WITHOUT = os.path.join(HERE, "data", "tiny.xplane.pb")
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NEW = [m for m in BENCH["per_layer"] if m["source"] == "program_span"
       and m["name"] != "guard.acquire_wait_ms"]
NAMES = ("engine.step", "engine.admit", "engine.consume", "engine.plan",
         "engine.dispatch", "engine.fetch", "engine.marshal", "engine.launch",
         "engine.device_wait", "guard.acquire", "guard.gated",
         "client.acquire")  # engine.tune only where a tuner is set


def _reader(name):
    return run.load_reader(os.path.join(REPO, "chipbench", "layer_metrics"),
                           name)


def _traced_run(monkeypatch, tmp_path, fixture):
    """A ``run`` as ``run.py`` hands one to a reader, its trace being the
    fixture."""
    where = tmp_path / "plugins" / "profile" / "recorded"
    where.mkdir(parents=True)
    shutil.copy(fixture, where / "tiny.xplane.pb")
    monkeypatch.setattr(_spans, "TRACE_DIR", str(tmp_path))
    return {"trace": object(), "pod_a": "serve-a",
            "record": {"backlog": True}}


def test_the_recorded_spans():
    spans = _spans.load(WITH_SPANS)
    w0, w1 = spans.window
    assert w1 > w0
    assert set(spans.host) >= set(NAMES)
    steps = spans.inside("engine.step")
    assert steps and [s.attrs["i"] for s in steps] == \
        sorted(s.attrs["i"] for s in steps)
    # every phase lies inside an engine.step of its own thread
    for name in NAMES[1:9]:
        for s in spans.inside(name):
            assert any(t.thread == s.thread and t.start <= s.start
                       and s.end <= t.end for t in spans.host["engine.step"]), name
    launches = spans.launches()
    assert launches and {s.attrs["kind"] for s in launches} <= {
        "prefill", "mixed", "decode"}
    assert all(s.attrs["pod"] == "default/serve-a"
               for s in spans.host["guard.gated"])
    assert {s.attrs["broker"] for s in spans.host["guard.acquire"]} <= {0, 1}
    # what ran on the device inside a gated interval is at most the interval
    for s in spans.inside("guard.gated"):
        assert 0 <= spans.busy.within(s.start, s.end) <= s.end - s.start
    # the engine's programs are told from everything else by name
    ours = {name.split("(")[0] for _, _, name in spans.modules
            if name.startswith(_spans.ENGINE_MODULE)}
    assert ours and all(n.endswith("_step") for n in ours)


def test_readers_over_the_recorded_spans(monkeypatch, tmp_path, capsys):
    traced = _traced_run(monkeypatch, tmp_path, WITH_SPANS)
    values = {}
    for key in ("schedule", "marshal", "fetch"):
        for cell in ("rate", "backlog"):
            values[key, cell] = _reader(
                f"engine.{key}_ms_per_dispatch.{cell}").read(traced)
        assert values[key, "rate"] == values[key, "backlog"] > 0
    spans = _spans.load(_spans.find_xplane(str(tmp_path)))
    n = len(spans.launches())
    assert values["marshal", "rate"] == pytest.approx(sum(
        s.end - s.start for s in spans.inside("engine.marshal")) / n * 1e3)
    idle = _reader("dispatch.gated_idle_ms.rate").read(traced)
    gated = spans.inside("guard.gated")
    assert 0 < idle <= sum(s.end - s.start for s in gated) / len(gated) * 1e3
    broker = _reader("guard.broker_wait_ms").read(traced)
    assert broker is None or broker > 0
    # pod A alone: there is no other pod's gated interval to read
    assert _reader("guard.cotenant_wall_over_device").read(traced) is None
    # were pod A someone else, the fixture's pod is the co-tenant
    other = dict(traced, pod_a="someone-else")
    assert _reader("dispatch.gated_idle_ms.rate").read(other) is None
    ratio = _reader("guard.cotenant_wall_over_device").read(other)
    assert ratio is None or ratio >= 1.0
    # one earlier line a run
    lines = [l for l in capsys.readouterr().out.splitlines()
             if "program_spans" in l]
    assert len(lines) == 1 and json.loads(lines[0])["launches"] == n


def test_a_program_without_spans_gives_nothing_and_does_not_raise(
        monkeypatch, tmp_path):
    traced = _traced_run(monkeypatch, tmp_path, WITHOUT)
    traced["record"] = {
        "backlog": False, "opened_at": 100.0, "seconds": 10.0,
        "sent": {"a": {"scored": True, "request": SimpleNamespace(due=1.0),
                       "result": SimpleNamespace(  # the parent's stamps
                           submitted_at=101.0, admitted_at=101.0,
                           first_token_at=101.5, finished_at=102.0)}}}
    for metric in NEW:
        assert _reader(metric["name"]).read(traced) is None, metric["name"]
    untraced = dict(traced, trace=None)
    assert _reader("engine.marshal_ms_per_dispatch.rate").read(untraced) is None


def _entry(due, submitted, admitted, dispatched, first, chunks=2):
    return {"scored": True, "request": SimpleNamespace(due=due),
            "result": SimpleNamespace(
                submitted_at=100 + submitted, admitted_at=100 + admitted,
                first_dispatch_at=100 + dispatched, prefill_chunks=chunks,
                first_token_at=None if first is None else 100 + first)}


def test_the_parts_of_the_slowest_tenth_add_up(capsys):
    sent = {str(i): _entry(i * 0.1, i * 0.1 + 0.01, i * 0.1 + 0.02,
                           i * 0.1 + 0.05, i * 0.1 + 0.2)
            for i in range(18)}
    # the slowest two of twenty; a third, slower still, got its first
    # token after the window closed and one never did: neither is read
    sent["slow"] = _entry(2.0, 2.03, 2.04, 2.5, 4.0, chunks=9)
    sent["slower"] = _entry(3.0, 3.01, 3.01, 3.21, 4.5, chunks=5)
    sent["after"] = _entry(9.0, 9.0, 9.1, 9.2, 10.5)
    sent["never"] = _entry(9.5, 9.5, 9.6, 9.7, None)
    sent["unscored"] = dict(_entry(9.9, 9.9, 9.9, 9.9, 9.95), scored=False)
    traced = {"record": {"backlog": False, "opened_at": 100.0,
                         "seconds": 10.0, "sent": sent}}
    parts = _spans.ttft_tail_parts(traced)
    assert parts["ttft"] == pytest.approx((2000 + 1500) / 2)
    assert parts["late"] == pytest.approx((30 + 10) / 2)
    assert parts["queue"] == pytest.approx((10 + 0) / 2)
    assert parts["prefill_wait"] == pytest.approx((460 + 200) / 2)
    assert parts["prefill"] == pytest.approx((1500 + 1290) / 2)
    assert parts["prefill_chunks"] == 7
    assert parts["late"] + parts["queue"] + parts["prefill_wait"] \
        + parts["prefill"] == pytest.approx(parts["ttft"])
    for part in ("queue", "prefill_wait", "prefill"):
        assert _reader(f"serving.ttft_tail_{part}_ms").read(traced) == \
            parts[part]
    said = [l for l in capsys.readouterr().out.splitlines()
            if "ttft_tail_parts_ms" in l]
    assert len(said) == 1 and json.loads(said[0])["requests"] == 2
    assert _spans.ttft_tail_parts(
        {"record": dict(traced["record"], backlog=True)}) is None


def test_every_new_metric_has_its_file_and_its_cells():
    assert len(NEW) == 13
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for metric in NEW:
        module = _reader(metric["name"])
        assert (module.LAYER, module.UNIT, module.MOVES) == \
            (metric["layer"], metric["unit"], metric["moves"])
        assert set(metric["workloads"]) <= set(e2e[metric["moves"]]["workloads"])
    # appended: what the benchmark had comes first, unchanged in order
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NEW[0]["name"]) == len(names) - 13
