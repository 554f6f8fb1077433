"""The readers of ``kubeshare.engine.retention``
(``layer_metrics/_retention.py``: ``step.retention_hbm_roofline.backlog``,
``retention.state_bytes_share.backlog``,
``retention.tail_rows_per_lane.backlog``, and ``step.stage_ms.retention.backlog``
beside them), on the CPU, in the style of ``test_diffusion_readers.py``: over
spans that carry the attributes, over spans that lack one, and over a program
without the span."""

import os
import shutil
from types import SimpleNamespace

import pytest

from chipbench import brumby_14b_base_roofline, roofline, run
from chipbench.layer_metrics import _spans, _stages

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DENSE_TRACE = os.path.join(HERE, "data", "tiny_spans.xplane.pb")
NAMES = ("step.stage_ms.retention.backlog",
         "step.retention_hbm_roofline.backlog",
         "retention.state_bytes_share.backlog",
         "retention.tail_rows_per_lane.backlog")
TC = run.load_json(REPO, "chipbench", "configs",
                   "brumby-14b-base.json")["transformer_config"]
# 30 decode lanes of which 24 hold a state, 4 steps, beside a chunk whose
# lane holds one too; one lane folded
MIXED = dict(lanes=31, state_lanes=24, passes=4, state_reads=97,
             tail_rows=30 * 4 * 250 + 700, folds=1, folded_rows=512,
             pages_freed=0, chunk=512)


def _reader(name):
    return run.load_reader(os.path.join(REPO, "chipbench", "layer_metrics"),
                           name)


def _run(monkeypatch, spans_attrs, counts=brumby_14b_base_roofline,
         stage_s=0.055):
    """A traced run of dispatches that each spent ``stage_s`` seconds of
    the device in stage ``retention``."""
    spans = _spans.Spans(
        window=(0.0, 10.0),
        host={"engine.retention": [
            _spans.Span(1.0 + i, 1.1 + i, "main", dict(attrs))
            for i, attrs in enumerate(spans_attrs)]},
        busy=None, modules=[])
    monkeypatch.setattr(_spans, "of", lambda run: spans)
    booked = _stages.Booked(
        [_stages.Launch(None, {"retention": stage_s, "ffn": 0.02},
                        busy_s=0.1) for _ in range(4)], 0.0)
    monkeypatch.setattr(_stages, "of", lambda run: booked)
    return {"trace": SimpleNamespace(step_busy_s={}),
            "record": {"steps": [], "decode_span": 4}, "tc": TC,
            "roofline": counts, "device_kind": "TPU v5 lite"}


def test_readers_over_spans_with_the_attributes(monkeypatch):
    traced = _run(monkeypatch, [MIXED] * 4)
    values = {name: _reader(name).read(traced) for name in NAMES}
    assert values[NAMES[0]] == pytest.approx(55.0)
    least = brumby_14b_base_roofline.retention_min_bytes(
        TC, 97, MIXED["tail_rows"], 1)
    assert least == 97 * 5 * 8 * 129 * 8320 * 4 \
        + MIXED["tail_rows"] * 20640 \
        + 2 * 5 * 8 * 129 * 8320 * 4 + 512 * 20640
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert values[NAMES[1]] == pytest.approx(least / peak / 0.055 * 100)
    assert 35 < values[NAMES[1]] < 45  # 17.6 GB in 55 ms of 819 GB/s
    weights = 4 * brumby_14b_base_roofline.decode_step_weight_bytes(TC)
    assert values[NAMES[2]] == pytest.approx(least / (least + weights) * 100)
    assert 45 < values[NAMES[2]] < 50
    assert values[NAMES[3]] == pytest.approx(
        MIXED["tail_rows"] / (30 * 4 + 1))
    # three spans for four booked launches (a tail's two ends): scaled to
    # the launches, the same
    fewer = _run(monkeypatch, [MIXED] * 3)
    assert _reader(NAMES[1]).read(fewer) == pytest.approx(values[NAMES[1]])


def test_spans_that_lack_what_a_reader_reads_give_nothing(monkeypatch):
    """A span without ``state_reads``, no pass of a span, a count of bytes
    without ``retention_min_bytes``, no second in the stage: None, never a
    raise."""
    older = {k: v for k, v in MIXED.items() if k != "state_reads"}
    traced = _run(monkeypatch, [older] * 4)
    assert all(_reader(name).read(traced) is None for name in NAMES[1:])
    chunks = dict(MIXED, lanes=1, state_lanes=0, passes=0, state_reads=1,
                  tail_rows=700)
    traced = _run(monkeypatch, [chunks] * 4)
    assert _reader(NAMES[2]).read(traced) is None  # no weight pass to share
    assert _reader(NAMES[3]).read(traced) == pytest.approx(700.0)
    traced = _run(monkeypatch, [MIXED] * 4, roofline)
    assert _reader(NAMES[1]).read(traced) is None
    assert _reader(NAMES[2]).read(traced) is None
    traced = _run(monkeypatch, [MIXED] * 4, stage_s=0.0)
    assert _reader(NAMES[0]).read(traced) is None
    assert _reader(NAMES[1]).read(traced) is None


def test_a_program_without_the_span_gives_nothing(monkeypatch, tmp_path):
    """An engine of another block (the recorded dense trace), and a run that
    was not traced; and each reader's file says what its entry says."""
    target = tmp_path / "plugins" / "profile" / "x"
    target.mkdir(parents=True)
    shutil.copy(DENSE_TRACE, target / "tiny.xplane.pb")
    monkeypatch.setattr(_spans, "TRACE_DIR", str(tmp_path))
    _spans.load.cache_clear()
    traced = {"trace": SimpleNamespace(step_busy_s={}),
              "record": {"steps": [], "decode_span": 4}, "tc": TC,
              "roofline": brumby_14b_base_roofline,
              "device_kind": "TPU v5 lite"}
    assert _spans.of(traced) is not None
    assert all(_reader(name).read(traced) is None for name in NAMES[1:])
    assert all(_reader(name).read({**traced, "trace": None}) is None
               for name in NAMES)
    for name in NAMES:
        module = _reader(name)
        entry = next(m for m in run.load_json(REPO, "BENCHMARK.json")
                     ["per_layer"] if m["name"] == name)
        assert (module.LAYER, module.UNIT, module.MOVES) \
            == (entry["layer"], entry["unit"], entry["moves"])
        assert entry["workloads"] == ["brumby-pp8.gen.topics"]
