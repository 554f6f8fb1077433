"""The readers of ``kubeshare.engine.conv`` and of stage ``conv``
(``layer_metrics/_conv.py``: ``step.conv_roofline.backlog``, and
``step.stage_ms.conv.backlog`` beside it), on the CPU, in the style of
``test_retention_readers.py``: over spans that carry the attributes, over
spans that lack one, and over a program without the span."""

import os
import shutil
from types import SimpleNamespace

import pytest

from chipbench import lfm2_24b_a2b_roofline, roofline, run
from chipbench.layer_metrics import _spans, _stages

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DENSE_TRACE = os.path.join(HERE, "data", "tiny_spans.xplane.pb")
NAMES = ("step.stage_ms.conv.backlog", "step.conv_roofline.backlog")
CELL = "lfm2-pp5.gen.topics"
TC = run.load_json(REPO, "chipbench", "configs",
                   "lfm2-24b-a2b.json")["transformer_config"]
# 31 decode lanes, 4 steps, beside a 512-row chunk past its prompt's first
MIXED = dict(lanes=32, passes=4, state_reads=6 * (31 * 4 + 1), resets=0,
             chunk=512)


def _reader(name):
    return run.load_reader(os.path.join(REPO, "chipbench", "layer_metrics"),
                           name)


def _run(monkeypatch, spans_attrs, counts=lfm2_24b_a2b_roofline,
         stage_s=0.004):
    """A traced run of dispatches that each spent ``stage_s`` seconds of
    the device in stage ``conv``."""
    spans = _spans.Spans(
        window=(0.0, 10.0),
        host={"engine.conv": [
            _spans.Span(1.0 + i, 1.1 + i, "main", dict(attrs))
            for i, attrs in enumerate(spans_attrs)]},
        busy=None, modules=[])
    monkeypatch.setattr(_spans, "of", lambda run: spans)
    booked = _stages.Booked(
        [_stages.Launch(None, {"conv": stage_s, "experts": 0.04},
                        busy_s=0.1) for _ in range(4)], 0.0)
    monkeypatch.setattr(_stages, "of", lambda run: booked)
    return {"trace": SimpleNamespace(step_busy_s={}),
            "record": {"steps": [], "decode_span": 4}, "tc": TC,
            "roofline": counts, "device_kind": "TPU v5 lite"}


def test_readers_over_spans_with_the_attributes(monkeypatch):
    traced = _run(monkeypatch, [MIXED] * 4)
    values = {name: _reader(name).read(traced) for name in NAMES}
    assert values[NAMES[0]] == pytest.approx(4.0)
    peaks = roofline.peaks("TPU v5 lite")
    # four passes of six operators' weights and 31 lanes' states at the HBM
    # rate, and a chunk's multiply-adds at the bf16 peak
    weights = 6 * (2048 * 6144 + 2048 * 2048 + 2048 * 3) * 2
    step = (weights + 2 * 31 * 49_152) / 819e9
    chunk = 6 * 512 * (2 * (2048 * 6144 + 2048 * 2048) + 8 * 2048) / 197e12
    least = lfm2_24b_a2b_roofline.conv_min_seconds(TC, peaks, 32, 4, 512)
    assert least == pytest.approx(4 * step + chunk)
    assert values[NAMES[1]] == pytest.approx(least / 0.004 * 100)
    assert 35 < values[NAMES[1]] < 45  # 1.0 + 0.53 ms of 4 ms
    # three spans for four booked launches (a tail's two ends): scaled to
    # the launches, the same
    fewer = _run(monkeypatch, [MIXED] * 3)
    assert _reader(NAMES[1]).read(fewer) == pytest.approx(values[NAMES[1]])
    # a decode span alone, a chunk alone
    alone = _run(monkeypatch, [dict(MIXED, lanes=31, chunk=0)] * 4)
    assert _reader(NAMES[1]).read(alone) == pytest.approx(
        4 * step / 0.004 * 100)
    first = _run(monkeypatch, [dict(lanes=1, passes=0, state_reads=0,
                                    resets=1, chunk=512)] * 4)
    assert _reader(NAMES[1]).read(first) == pytest.approx(
        chunk / 0.004 * 100)


def test_spans_that_lack_what_a_reader_reads_give_nothing(monkeypatch):
    """A span without ``passes``, a count of bytes without
    ``conv_min_seconds``, no second in the stage: None, never a raise."""
    older = {k: v for k, v in MIXED.items() if k != "passes"}
    traced = _run(monkeypatch, [older] * 4)
    assert _reader(NAMES[1]).read(traced) is None
    traced = _run(monkeypatch, [MIXED] * 4, roofline)
    assert _reader(NAMES[1]).read(traced) is None
    traced = _run(monkeypatch, [MIXED] * 4, stage_s=0.0)
    assert all(_reader(name).read(traced) is None for name in NAMES)


def test_a_program_without_the_span_gives_nothing(monkeypatch, tmp_path):
    """An engine of another model (the recorded dense trace), and a run that
    was not traced; and each reader's file says what its entry says."""
    target = tmp_path / "plugins" / "profile" / "x"
    target.mkdir(parents=True)
    shutil.copy(DENSE_TRACE, target / "tiny.xplane.pb")
    monkeypatch.setattr(_spans, "TRACE_DIR", str(tmp_path))
    _spans.load.cache_clear()
    traced = {"trace": SimpleNamespace(step_busy_s={}),
              "record": {"steps": [], "decode_span": 4}, "tc": TC,
              "roofline": lfm2_24b_a2b_roofline,
              "device_kind": "TPU v5 lite"}
    assert _spans.of(traced) is not None
    assert _reader(NAMES[1]).read(traced) is None
    assert all(_reader(name).read({**traced, "trace": None}) is None
               for name in NAMES)
    for name in NAMES:
        module = _reader(name)
        entry = next(m for m in run.load_json(REPO, "BENCHMARK.json")
                     ["per_layer"] if m["name"] == name)
        assert (module.LAYER, module.UNIT, module.MOVES) \
            == (entry["layer"], entry["unit"], entry["moves"])
        assert entry["workloads"] == [CELL]
        assert entry["source"] == "device_trace"
