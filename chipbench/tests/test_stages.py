"""The readers of device time by stage (``layer_metrics/_stages.py``: the
``step.stage_ms.*``, ``step.stage_unscoped_share.*``,
``step.experts_hbm_roofline.backlog`` and ``step.attend_kernel_hbm_roofline.*``
metrics), on the CPU, over hand-made events in the style of ``test_tiles.py``:
a ``while`` and its body, two launches of different programs whose
instruction names collide, a launch whose program no table is known for, and
the programs and runs that give nothing to read."""

import json
import os

import pytest

from chipbench import joyai_llm_flash_roofline, roofline, run
from chipbench.layer_metrics import _spans, _stages
from chipbench.trace import Covered

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TC = run.load_json(REPO, "chipbench", "configs",
                   "joyai-llm-flash.json")["transformer_config"]
BENCH = run.load_json(REPO, "BENCHMARK.json")
NAMES = [m["name"] for m in BENCH["per_layer"]
         if m["name"].startswith(("step.stage_", "step.experts_hbm_",
                                  "step.attend_kernel_"))]
# two programs whose instruction names collide: `fusion.1` is the experts'
# in the one and the head's in the other
TABLES = {
    "mixed/512": {"while.1": "experts", "fusion.1": "experts",
                  "fusion.2": "ffn", "paged_latent_decode_attention.3":
                  "attention", "fusion.4": "attention",
                  "dynamic-update-slice.5": "kv_write", "copy.6": "unscoped"},
    "decode/0": {"fusion.1": "head", "paged_latent_decode_attention.3":
                 "attention"},
}


def _reader(name):
    return run.load_reader(os.path.join(REPO, "chipbench", "layer_metrics"),
                           name)


def _launch(start, kind, program, behind=-0.001, **attrs):
    """A launch, its device_wait and its module: 2 ms of launch, the module
    from 1 ms in to 1 ms before the wait's end — or, where the device's
    clock runs ``behind`` the host's, from before the launch's start."""
    base = dict(kind=kind, lanes=2, rows=1000, chunk=0, attend="kernel")
    if program is not None:
        base["program"] = program
    launch = _spans.Span(start, start + 0.002, "main", {**base, **attrs})
    wait = _spans.Span(start + 0.002, start + 0.100, "main", {})
    module = (start - behind, start + 0.099, f"jit_kubeshare_{kind}_step(7)")
    return launch, wait, module


def _traced(launches, ops, routing=()):
    spans = _spans.Spans(
        window=(0.0, 10.0),
        host={"engine.launch": [l for l, _, _ in launches],
              "engine.device_wait": [w for _, w, _ in launches],
              "engine.routing": [_spans.Span(9.0, 9.001, "main", dict(a))
                                 for a in routing],
              "engine.admit": [_spans.Span(0.5, 0.501, "main", dict(
                  queued=3, admitted=1, matched_rows=16))]},
        busy=Covered((s, e) for s, e, _ in ops),
        modules=[m for _, _, m in launches]
        + [(5.0, 5.5, "jit_cotenant_step(9)")])
    return spans, sorted(ops)


# the mixed launch at 1.0: a while of 40 ms whose body's two fusions cover 30
# of them, a kernel call, a write, an operation the table does not know, and
# one no scope names; the decode launch at 2.0: the same names, other stages
MIXED = [(1.010, 1.050, "while.1"), (1.012, 1.027, "fusion.1"),
         (1.030, 1.045, "fusion.2"),
         (1.050, 1.060, "paged_latent_decode_attention.3"),
         (1.060, 1.065, "fusion.4"), (1.065, 1.075, "dynamic-update-slice.5"),
         (1.075, 1.078, "copy.6"), (1.078, 1.080, "not-in-the-table.9")]
DECODE = [(2.010, 2.030, "fusion.1"),
          (2.030, 2.040, "paged_latent_decode_attention.3")]
ROUTING = dict(rows=640, passes=5, held=100, zero=0, absent=0, touched=40)


def test_a_while_keeps_what_its_body_does_not_cover_and_names_collide():
    # the decode launch's module begins half a millisecond BEFORE its span
    # on the trace's clock, as one did on the chip: it is still that launch's
    launches = [_launch(1.0, "mixed", "mixed/512", rows=3000, chunk=512),
                _launch(2.0, "decode", "decode/0", behind=0.0005, rows=2000)]
    spans, ops = _traced(launches, MIXED + DECODE + [(5.1, 5.4, "fusion.1")])
    booked = _stages.book(spans, ops, TABLES.get)
    mixed, decode = booked.launches
    assert mixed.stages == pytest.approx({
        "experts": 0.010 + 0.015,  # the while's own 10 ms, its fusion.1
        "ffn": 0.015, "attention": 0.015, "kv_write": 0.010,
        "unscoped": 0.005})  # copy.6, and the name the table lacks
    assert mixed.kernel_s == pytest.approx(0.010)
    # the same names in the other program are the other program's stages;
    # the co-tenant's fusion.1 at 5.1 s is in no engine module: nobody's
    assert decode.stages == pytest.approx({"head": 0.020, "attention": 0.010})
    assert decode.kernel_s == pytest.approx(0.010)
    for launch in booked.launches:
        assert sum(launch.stages.values()) == pytest.approx(launch.busy_s)
    assert _stages.instruction_name(
        "%fusion.12 = bf16[128,768]{1,0:T(8,128)(2,1)} fusion(%a), "
        "kind=kLoop") == "fusion.12"
    assert _stages.instruction_name("while.2") == "while.2"


def test_what_gives_nothing_to_read():
    known = _launch(1.0, "mixed", "mixed/512")
    # a launch whose program no table is known for
    spans, ops = _traced([known, _launch(2.0, "decode", "decode/1")],
                         MIXED + DECODE)
    assert _stages.book(spans, ops, TABLES.get) is None
    # launches without `program`: the parent of the PR that brought it
    spans, ops = _traced([_launch(1.0, "mixed", None)], MIXED)
    assert _stages.book(spans, ops, TABLES.get) is None
    # a copy-on-write or an upload is no planned launch: it is not booked,
    # and needs no table
    spans, ops = _traced([known, _launch(3.0, "copy", "copy/0")], MIXED)
    assert len(_stages.book(spans, ops, TABLES.get).launches) == 1
    # a launch the window closed on (no device_wait after it) is left out
    cut = _launch(2.0, "decode", "decode/0")
    spans, ops = _traced([known, cut], MIXED + DECODE)
    spans.host["engine.device_wait"] = [known[1]]
    assert [l.span for l in _stages.book(spans, ops, TABLES.get).launches] \
        == [known[0]]
    # nothing launched in the tail
    spans, ops = _traced([], [])
    assert _stages.book(spans, ops, TABLES.get) is None


def test_a_recorded_trace_from_before_the_table_gives_nothing():
    """``data/tiny_spans.xplane.pb`` (PR 24, on a TPU v5e): its ``XLA Ops``
    events give their instructions' names, and its launches name no
    program, which is what the parent of PR 38 gives these readers."""
    recorded = os.path.join(HERE, "data", "tiny_spans.xplane.pb")
    ops = _stages.load_ops(recorded)
    assert len(ops) > 100 and ops == sorted(ops)
    names = {name for _, _, name in ops}
    assert all(" " not in n and not n.startswith("%") for n in names)
    assert any(n.startswith("fusion") for n in names)
    spans = _spans.load(recorded)
    assert spans.launches()
    assert _stages.book(spans, ops, TABLES.get) is None


def _run(monkeypatch, launches, ops, routing, tables=TABLES.get,
         counts=joyai_llm_flash_roofline):
    spans, ops = _traced(launches, ops, routing)
    monkeypatch.setattr(_spans, "of", lambda run: spans)
    monkeypatch.setattr(_stages, "find_xplane", lambda directory: "hand-made")
    monkeypatch.setattr(_stages, "load_ops", lambda path: ops)
    monkeypatch.setattr(_stages, "_program_tables", lambda: tables)
    _stages._booked.clear()
    return {"trace": object(), "record": {"decode_span": 4}, "tc": TC,
            "roofline": counts, "device_kind": "TPU v5 lite"}


def test_the_readers_over_booked_launches(monkeypatch, capsys):
    launches = [_launch(1.0, "mixed", "mixed/512", rows=3000, chunk=512),
                _launch(2.0, "decode", "decode/0", rows=2000)]
    traced = _run(monkeypatch, launches, MIXED + DECODE, [ROUTING, ROUTING])
    values = {name: _reader(name).read(traced) for name in NAMES}
    assert len(values) == 14
    for cell in ("rate", "backlog"):
        assert values[f"step.stage_ms.attention.{cell}"] == \
            pytest.approx((15 + 10) / 2)
        assert values[f"step.stage_ms.kv_write.{cell}"] == pytest.approx(5)
        assert values[f"step.stage_ms.ffn.{cell}"] == pytest.approx(7.5)
        assert values[f"step.stage_ms.head.{cell}"] == pytest.approx(10)
        assert values[f"step.stage_unscoped_share.{cell}"] == \
            pytest.approx(0.005 / (0.070 + 0.030) * 100)
    assert values["step.stage_ms.experts.backlog"] == pytest.approx(12.5)
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    # two routing spans for two launches: 80 touched experts in 25 ms
    assert values["step.experts_hbm_roofline.backlog"] == pytest.approx(
        80 * joyai_llm_flash_roofline.expert_bytes(TC) / peak / 0.025 * 100)
    # the mixed launch's lanes ran 4 kernel passes, the decode span's too
    row = joyai_llm_flash_roofline.kv_read_bytes_per_row(TC)
    for cell in ("rate", "backlog"):
        assert values[f"step.attend_kernel_hbm_roofline.{cell}"] == \
            pytest.approx((3000 + 2000) * 4 * row / peak / 0.020 * 100)
    # one earlier line a run, whatever the number of readers
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if "program_stages" in l]
    assert len(said) == 1
    assert said[0]["launches"] == {"mixed": 1, "decode": 1}
    assert said[0]["program_stages"]["decode"] == {"attention": 0.01,
                                                   "head": 0.02}
    assert said[0]["module_busy_s"] == pytest.approx(0.100)
    assert said[0]["kernel_s"] == pytest.approx(0.020)
    assert said[0]["programs"] == ["decode/0", "mixed/512"]
    assert said[0]["admit"] == {"calls": 1, "queued": 3, "admitted": 1,
                                "matched_rows": 16}
    assert said[0]["table_build_s"] >= 0


def test_a_diffusion_pass_is_one_kernel_pass_and_a_loop_lane_is_left_out(
        monkeypatch):
    """A diffusion dispatch runs the kernel once over its lanes' rows; a
    launch whose lanes ran the key-block loop (``attend`` = ``blocks``), or
    that had no lane, adds neither bytes nor seconds; a configuration whose
    count of bytes has no ``kv_read_bytes_per_row`` gives its
    ``kv_bytes_per_row``, one without experts no expert roofline."""
    tables = {"diffusion/0": TABLES["decode/0"], "mixed/512":
              TABLES["mixed/512"], "prefill/512": TABLES["mixed/512"]}
    launches = [
        _launch(1.0, "mixed", "mixed/512", attend="blocks", rows=9000),
        _launch(2.0, "diffusion", "diffusion/0", rows=2000),
        _launch(3.0, "prefill", "prefill/512", lanes=0, rows=0, chunk=512)]
    ops = MIXED + DECODE + [(s + 2.0, e + 2.0, n) for s, e, n in MIXED]
    traced = _run(monkeypatch, launches, ops, [ROUTING], tables.get, roofline)
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert _reader("step.attend_kernel_hbm_roofline.backlog").read(traced) \
        == pytest.approx(2000 * roofline.kv_bytes_per_row(TC) / peak
                         / 0.010 * 100)
    assert _reader("step.experts_hbm_roofline.backlog").read(traced) is None
    assert _reader("step.stage_ms.experts.backlog").read(traced) == \
        pytest.approx(2 * 25 / 3)


def test_a_program_or_a_run_without_the_table_gives_nothing(monkeypatch):
    launches = [_launch(1.0, "mixed", "mixed/512")]
    # the parent's program: no serving.stages to import
    traced = _run(monkeypatch, launches, MIXED, [ROUTING], tables=None)
    for name in NAMES:
        assert _reader(name).read(traced) is None, name
    # its launches name no program
    traced = _run(monkeypatch, [_launch(1.0, "mixed", None)], MIXED,
                  [ROUTING])
    for name in NAMES:
        assert _reader(name).read(traced) is None, name
    # a dense engine: no routing span, no expert stage
    dense = {"mixed/512": {"fusion.1": "ffn", "fusion.2": "ffn"}}
    traced = _run(monkeypatch, launches, MIXED, [], dense.get, roofline)
    assert _reader("step.stage_ms.experts.backlog").read(traced) is None
    assert _reader("step.experts_hbm_roofline.backlog").read(traced) is None
    assert _reader("step.stage_ms.ffn.backlog").read(traced) == \
        pytest.approx(30)
    assert _reader("step.attend_kernel_hbm_roofline.rate").read(traced) is None
    # a run that was not traced
    monkeypatch.undo()
    _stages._booked.clear()
    for name in NAMES:
        assert _reader(name).read({"trace": None}) is None, name


def test_every_stage_metric_has_its_file_and_its_cells():
    assert len(NAMES) == 14
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert [m["name"] for m in BENCH["per_layer"]][-14:] == NAMES  # appended
    routed = {"lcf-ep32.gen.topics", "joyai-pp8.gen.topics",
              "sdar-pp8.gen.topics"}
    for name in NAMES:
        metric, module = per_layer[name], _reader(name)
        assert (module.LAYER, module.UNIT, module.MOVES) == \
            (metric["layer"], metric["unit"], metric["moves"])
        assert metric["source"] == "device_trace"
        cells = set(metric["workloads"])
        assert cells <= set(e2e[metric["moves"]]["workloads"])
        assert "scb-1b.gen.shared" not in cells
        if name.endswith(".rate"):
            assert cells == {"scb-1b.gen.rate"}
        elif "experts" in name:
            assert cells == routed
        elif ".ffn." in name:  # `sdar`'s every feed-forward is the experts'
            assert cells == routed - {"sdar-pp8.gen.topics"} \
                | {"sc2-3b.gen.backlog"}
        else:
            assert cells == routed | {"sc2-3b.gen.backlog"}
