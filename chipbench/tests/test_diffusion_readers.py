"""The readers of ``kubeshare.engine.diffusion``
(``layer_metrics/_diffusion.py``: ``diffusion.rows_per_token.backlog``,
``diffusion.passes_per_block.backlog``, ``step.diffusion_device_ms.backlog``,
``step.diffusion_routed_hbm_roofline.backlog``,
``step.mixed_diffusion_routed_hbm_roofline.backlog``), on the CPU, in the style of
``test_tiles.py``: over spans that carry the attributes, over spans that lack
one, and over a program without the span."""

import os
import shutil
from types import SimpleNamespace

import pytest

from chipbench import roofline, run, sdar_30b_a3b_chat_roofline
from chipbench.layer_metrics import _spans

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DENSE_TRACE = os.path.join(HERE, "data", "tiny_spans.xplane.pb")
NAMES = ("diffusion.rows_per_token.backlog",
         "diffusion.passes_per_block.backlog",
         "step.diffusion_device_ms.backlog",
         "step.diffusion_routed_hbm_roofline.backlog",
         "step.mixed_diffusion_routed_hbm_roofline.backlog")
TC = run.load_json(REPO, "chipbench", "configs",
                   "sdar-30b-a3b-chat.json")["transformer_config"]
# 32 lanes of which 26 denoise and 6 commit: 128 rows, 26 tokens
ALONE = dict(lanes=32, passes=26, commit_passes=6, rows=128, masked_rows=65,
             committed=26, blocks_done=6, kv_rows=40000, chunk=0, touched=766)
MIXED = dict(ALONE, chunk=512, touched=1534)


def _reader(name):
    return run.load_reader(os.path.join(REPO, "chipbench", "layer_metrics"),
                           name)


def _run(monkeypatch, spans_attrs, counts=sdar_30b_a3b_chat_roofline):
    """A traced run of four dispatches that carried lanes alone (the
    harness's kind ``decode``) and one that carried a chunk too."""
    spans = _spans.Spans(
        window=(0.0, 10.0),
        host={"engine.diffusion": [
            _spans.Span(1.0 + i, 1.1 + i, "main", dict(attrs))
            for i, attrs in enumerate(spans_attrs)]},
        busy=None, modules=[])
    monkeypatch.setattr(_spans, "of", lambda run: spans)
    steps = [{"kind": "decode", "i": i, "rows": []} for i in range(4)] \
        + [{"kind": "mixed", "i": 4, "rows": []}]
    busy = {0: 0.025, 1: 0.025, 2: 0.025, 3: 0.025, 4: 0.045}
    return {"trace": SimpleNamespace(step_busy_s=busy),
            "record": {"steps": steps, "decode_span": 4}, "tc": TC,
            "roofline": counts, "device_kind": "TPU v5 lite"}


def test_readers_over_spans_with_the_attributes(monkeypatch):
    traced = _run(monkeypatch, [ALONE] * 4 + [MIXED])
    values = {name: _reader(name).read(traced) for name in NAMES}
    assert values[NAMES[0]] == pytest.approx(128 * 5 / (26 * 5))
    assert values[NAMES[1]] == pytest.approx(32 * 5 / (6 * 5))
    assert values[NAMES[2]] == pytest.approx(25.0)
    least = sdar_30b_a3b_chat_roofline.pass_min_bytes(TC, 40000, 766)
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert values[NAMES[3]] == pytest.approx(least / peak / 0.025 * 100)
    assert 35 < values[NAMES[3]] < 45  # 8.6 GB in 25 ms of 819 GB/s
    # the mixed dispatch: the weights outside the experts once, the lanes'
    # rows, and each touched pair once (the two passes count it twice)
    least = sdar_30b_a3b_chat_roofline.pass_min_bytes(TC, 40000, 767)
    assert values[NAMES[4]] == pytest.approx(least / peak / 0.045 * 100)
    assert 20 < values[NAMES[4]] < 25
    # three spans for four timed steps (a tail's two ends): scaled, the same
    fewer = _run(monkeypatch, [ALONE] * 3 + [MIXED])
    assert _reader(NAMES[3]).read(fewer) == pytest.approx(values[NAMES[3]])
    # it reads its own dispatches only: the mixed one's bytes are not in it
    assert _reader(NAMES[3]).read(
        _run(monkeypatch, [ALONE] * 4 + [dict(MIXED, touched=10 ** 6)])) \
        == pytest.approx(values[NAMES[3]])


def test_spans_that_lack_what_a_reader_reads_give_nothing(monkeypatch):
    """A span without ``touched`` (a program from before it carried it), no
    token served, no block done, no dispatch without a chunk, a count of
    bytes without ``pass_min_bytes``: None, never a raise."""
    older = {k: v for k, v in ALONE.items() if k != "touched"}
    traced = _run(monkeypatch, [older] * 4)
    assert all(_reader(name).read(traced) is None for name in NAMES)
    idle = dict(ALONE, committed=0, blocks_done=0)
    traced = _run(monkeypatch, [idle] * 4)
    assert _reader(NAMES[0]).read(traced) is None
    assert _reader(NAMES[1]).read(traced) is None
    assert _reader(NAMES[2]).read(traced) == pytest.approx(25.0)
    assert _reader(NAMES[4]).read(traced) is None  # no span with a chunk
    traced = _run(monkeypatch, [MIXED] * 4)
    assert _reader(NAMES[2]).read(traced) is None
    assert _reader(NAMES[3]).read(traced) is None
    assert _reader(NAMES[4]).read(traced) is not None
    assert _reader(NAMES[0]).read(traced) == pytest.approx(128 / 26)
    traced = _run(monkeypatch, [ALONE] * 4 + [MIXED], roofline)
    assert _reader(NAMES[3]).read(traced) is None
    assert _reader(NAMES[4]).read(traced) is None
    assert _reader(NAMES[2]).read(traced) == pytest.approx(25.0)


def test_a_program_without_the_span_gives_nothing(monkeypatch, tmp_path):
    """An engine that generates one token after another (the recorded dense
    trace), and a run that was not traced."""
    target = tmp_path / "plugins" / "profile" / "x"
    target.mkdir(parents=True)
    shutil.copy(DENSE_TRACE, target / "tiny.xplane.pb")
    monkeypatch.setattr(_spans, "TRACE_DIR", str(tmp_path))
    _spans.load.cache_clear()
    traced = {"trace": SimpleNamespace(step_busy_s={}),
              "record": {"steps": [], "decode_span": 4}, "tc": TC,
              "roofline": sdar_30b_a3b_chat_roofline,
              "device_kind": "TPU v5 lite"}
    assert _spans.of(traced) is not None
    assert all(_reader(name).read(traced) is None for name in NAMES)
    assert all(_reader(name).read({**traced, "trace": None}) is None
               for name in NAMES)
    for name in NAMES:
        module = _reader(name)
        entry = next(m for m in run.load_json(REPO, "BENCHMARK.json")
                     ["per_layer"] if m["name"] == name)
        assert (module.LAYER, module.UNIT, module.MOVES) \
            == (entry["layer"], entry["unit"], entry["moves"])
        assert entry["workloads"] == ["sdar-pp8.gen.topics"]
