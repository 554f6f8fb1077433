"""The readers of the routing spans' newer attributes
(``layer_metrics/_tiles.py``: ``moe.rows_per_touched_expert.backlog``,
``moe.tile_fill_share.backlog``, ``step.mixed_expert_bytes_share.backlog``),
on the CPU, in the style of ``test_spans.py``: over spans that carry the
attributes, over the spans of a program from before they existed, and over a
program without the span."""

import os
import shutil
from types import SimpleNamespace

import pytest

from chipbench import joyai_llm_flash_roofline, roofline, run
from chipbench.layer_metrics import _spans

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DENSE_TRACE = os.path.join(HERE, "data", "tiny_spans.xplane.pb")
NAMES = ("moe.rows_per_touched_expert.backlog", "moe.tile_fill_share.backlog",
         "step.mixed_expert_bytes_share.backlog")
TC = run.load_json(REPO, "chipbench", "configs",
                   "joyai-llm-flash.json")["transformer_config"]
OLDER = dict(rows=640, passes=5, held=18944, zero=0, absent=0, touched=2944)
NEWER = dict(live=592, tiles=2944, tile_rows=192512)


def _reader(name):
    return run.load_reader(os.path.join(REPO, "chipbench", "layer_metrics"),
                           name)


def _run(monkeypatch, attrs, counts=joyai_llm_flash_roofline):
    """A traced run of two mixed dispatches, each with a routing span."""
    spans = _spans.Spans(
        window=(0.0, 10.0),
        host={"engine.routing": [_spans.Span(1.0 + i, 1.1 + i, "main",
                                             dict(attrs)) for i in range(2)]},
        busy=None, modules=[])
    monkeypatch.setattr(_spans, "of", lambda run: spans)
    steps = [{"kind": "mixed", "i": i, "rows": [1000] * 20} for i in range(2)]
    return {"trace": SimpleNamespace(step_busy_s={0: 0.1, 1: 0.1}),
            "record": {"steps": steps, "decode_span": 4}, "tc": TC,
            "roofline": counts, "device_kind": "TPU v5 lite"}


def test_readers_over_spans_with_the_newer_attributes(monkeypatch):
    traced = _run(monkeypatch, {**OLDER, **NEWER})
    values = {name: _reader(name).read(traced) for name in NAMES}
    assert values[NAMES[0]] == pytest.approx(18944 / 2944)
    assert values[NAMES[1]] == pytest.approx(18944 / 192512 * 100)
    experts = 2944 * joyai_llm_flash_roofline.expert_bytes(TC)
    outside = 4 * joyai_llm_flash_roofline.decode_step_min_bytes(TC, 20000)
    assert values[NAMES[2]] == pytest.approx(
        experts / (experts + outside) * 100)
    assert 85 < values[NAMES[2]] < 92
    # the share is of the very bytes the routed roofline divides by
    routed = _reader("step.mixed_routed_hbm_roofline.backlog").read(traced)
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert routed == pytest.approx(
        2 * (experts + outside) / peak / 0.2 * 100)


def test_the_parents_spans_read_what_they_carry(monkeypatch):
    """Spans from before ``live`` / ``tiles`` / ``tile_rows`` (the parent of
    the PR that brought them): the tile reader finds nothing and does not
    raise; the two that need only the older counts still read."""
    traced = _run(monkeypatch, OLDER)
    assert _reader(NAMES[1]).read(traced) is None
    assert _reader(NAMES[0]).read(traced) == pytest.approx(18944 / 2944)
    assert _reader(NAMES[2]).read(traced) > 0
    # a configuration whose count of bytes has no expert in it
    assert _reader(NAMES[2]).read(_run(monkeypatch, OLDER, roofline)) is None
    # no expert was touched: nothing to divide by
    idle = {**OLDER, **NEWER, "held": 0, "touched": 0, "tile_rows": 0}
    assert _reader(NAMES[0]).read(_run(monkeypatch, idle)) is None
    assert _reader(NAMES[1]).read(_run(monkeypatch, idle)) is None


def test_a_program_without_the_span_gives_nothing(monkeypatch, tmp_path):
    """The dense block's engine, recorded on the chip (PR 24): spans, but
    no ``kubeshare.engine.routing``."""
    where = tmp_path / "plugins" / "profile" / "recorded"
    where.mkdir(parents=True)
    shutil.copy(DENSE_TRACE, where / "tiny.xplane.pb")
    monkeypatch.setattr(_spans, "TRACE_DIR", str(tmp_path))
    traced = {"trace": object(), "pod_a": "serve-a", "tc": TC,
              "record": {"backlog": True, "steps": [], "decode_span": 4},
              "roofline": joyai_llm_flash_roofline,
              "device_kind": "TPU v5 lite"}
    for name in NAMES:
        assert _reader(name).read(traced) is None, name
        assert _reader(name).read(dict(traced, trace=None)) is None, name
