"""The readers of a cache by layer kind (``layer_metrics/_kinds.py``:
``step.mixed_kinds_routed_hbm_roofline.backlog``,
``step.attend_kinds_kernel_hbm_roofline.backlog``,
``kv.window_read_share.backlog``, ``kv.pool_bytes_per_context_row.backlog``),
on the CPU, in the style of ``test_conv_readers.py``: over spans that carry
the attributes, over spans that lack one, and over a program without the
span."""

import os
import shutil
from types import SimpleNamespace

import pytest

from chipbench import roofline, run, smallthinker_21ba3b_roofline
from chipbench.layer_metrics import _spans, _stages

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DENSE_TRACE = os.path.join(HERE, "data", "tiny_spans.xplane.pb")
MIXED, KERNEL, SHARE, POOL = NAMES = (
    "step.mixed_kinds_routed_hbm_roofline.backlog",
    "step.attend_kinds_kernel_hbm_roofline.backlog",
    "kv.window_read_share.backlog",
    "kv.pool_bytes_per_context_row.backlog")
CELL = "smallthinker-pp7.gen.longmix"
CONFIG_FILE = run.load_json(REPO, "chipbench", "configs",
                            "smallthinker-21ba3b-instruct.json")
TC = CONFIG_FILE["transformer_config"]
# 22 decode lanes holding 140,000 rows between them, 50,000 of them inside
# their windows, beside a 512-row chunk
LAUNCH = dict(kind="mixed", lanes=22, rows=140_000, window_rows=50_000,
              chunk=512, attend="kernel", program="mixed/512")
KINDS = dict(released=5, drawn=5, live_full=9000, live_window=5000,
             context_rows=140_000)
ROUTING = dict(rows=640, passes=4, held=6 * 8 * 600, zero=0, absent=0,
               touched=1200)


def _reader(name):
    return run.load_reader(os.path.join(REPO, "chipbench", "layer_metrics"),
                           name)


def _run(monkeypatch, launch=LAUNCH, kinds=KINDS,
         counts=smallthinker_21ba3b_roofline, busy_s=0.040, kernel_s=0.008,
         n=4):
    """A traced run of ``n`` mixed dispatches, each ``busy_s`` seconds of
    the device of which ``kernel_s`` in the paged kernel."""
    span = lambda i, attrs: _spans.Span(1.0 + i, 1.1 + i, "main",
                                        dict(attrs))
    host = {"engine.routing": [span(i, ROUTING) for i in range(n)]}
    if launch is not None:
        host["engine.launch"] = [span(i, launch) for i in range(n)]
    if kinds is not None:
        host["engine.kv_kinds"] = [span(i, kinds) for i in range(n)]
    spans = _spans.Spans(window=(0.0, 10.0), host=host, busy=None,
                         modules=[])
    monkeypatch.setattr(_spans, "of", lambda run: spans)
    booked = _stages.Booked(
        [_stages.Launch(s, {"attention": 2 * kernel_s, "experts": 0.01},
                        kernel_s=kernel_s, busy_s=busy_s)
         for s in host.get("engine.launch", [])], 0.0)
    monkeypatch.setattr(_stages, "of",
                        lambda run: booked if booked.launches else None)
    return {"trace": SimpleNamespace(step_busy_s={}),
            "record": {"steps": [], "decode_span": 4}, "tc": TC,
            "roofline": counts, "device_kind": "TPU v5 lite",
            "cell": {"config_file": CONFIG_FILE}}


def test_readers_over_spans_with_the_attributes(monkeypatch):
    traced = _run(monkeypatch)
    values = {name: _reader(name).read(traced) for name in NAMES}
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert peak == 819e9
    assert values[SHARE] == pytest.approx(50 / 1.4)
    # a full page is 2 layers' rows, a window page 6 layers'
    held = (9000 * 4096 + 5000 * 12288) * 16
    assert values[POOL] == pytest.approx(held / 140_000)
    assert values[POOL] < 16384
    rows = 140_000 * 4096 + 50_000 * 12288
    assert values[KERNEL] == pytest.approx(4 * rows / peak / 0.008 * 100)
    weights = smallthinker_21ba3b_roofline.decode_step_weight_bytes(TC)
    assert weights == 1_116_165_120
    least = 4 * (weights + rows) + 1200 * 11_796_480
    assert values[MIXED] == pytest.approx(least / peak / 0.040 * 100)
    assert 0 < values[MIXED] < 100 and 0 < values[KERNEL] < 100
    # counted as every layer reading every held row the kernel's share would
    # read past what the chip can do: what the cell's own readers are for
    assert 4 * 140_000 * 16384 / peak / 0.008 * 100 > 100
    # three routing spans for four booked launches (a tail's two ends)
    fewer = _run(monkeypatch)
    _spans.of(fewer).host["engine.routing"].pop()
    assert _reader(MIXED).read(fewer) == pytest.approx(
        (4 * (weights + rows) + 1200 * 11_796_480) / peak / 0.040 * 100)
    # a window that never binds reads 100, and every layer's row a row
    whole = _run(monkeypatch, dict(LAUNCH, window_rows=140_000),
                 dict(KINDS, live_full=140_000 // 16,
                      live_window=140_000 // 16))
    assert _reader(SHARE).read(whole) == pytest.approx(100.0)
    assert _reader(POOL).read(whole) == pytest.approx(16384.0)
    # a launch whose lanes ran the loop is not the kernel's; one with no
    # lane is nobody's
    loop = _run(monkeypatch, dict(LAUNCH, attend="blocks"))
    assert _reader(KERNEL).read(loop) is None
    assert _reader(MIXED).read(loop) == pytest.approx(values[MIXED])
    chunk = _run(monkeypatch, dict(LAUNCH, kind="prefill", lanes=0, rows=0,
                                   window_rows=0))
    assert all(_reader(name).read(chunk) is None
               for name in (MIXED, KERNEL, SHARE))


def test_spans_that_lack_what_a_reader_reads_give_nothing(monkeypatch):
    """A launch span without ``window_rows`` (every engine of a model that
    caches under one table; the parent), a ``kv_kinds`` span without a
    count, a count of bytes without the functions, no second of the device:
    None, never a raise."""
    older = {k: v for k, v in LAUNCH.items() if k != "window_rows"}
    traced = _run(monkeypatch, older)
    assert all(_reader(name).read(traced) is None
               for name in (MIXED, KERNEL, SHARE))
    assert _reader(POOL).read(traced) is not None
    traced = _run(monkeypatch, kinds={k: v for k, v in KINDS.items()
                                      if k != "live_window"})
    assert _reader(POOL).read(traced) is None
    traced = _run(monkeypatch, kinds=dict(KINDS, context_rows=0))
    assert _reader(POOL).read(traced) is None
    traced = _run(monkeypatch, counts=roofline)
    assert all(_reader(name).read(traced) is None
               for name in (MIXED, KERNEL, POOL))
    traced = _run(monkeypatch, busy_s=0.0, kernel_s=0.0)
    assert _reader(MIXED).read(traced) is None
    assert _reader(KERNEL).read(traced) is None


def test_a_program_without_the_span_gives_nothing(monkeypatch, tmp_path):
    """An engine of another model (the recorded dense trace: launches
    without ``window_rows``, no ``kv_kinds`` span), and a run that was not
    traced; and each reader's file says what its entry says."""
    target = tmp_path / "plugins" / "profile" / "x"
    target.mkdir(parents=True)
    shutil.copy(DENSE_TRACE, target / "tiny.xplane.pb")
    monkeypatch.setattr(_spans, "TRACE_DIR", str(tmp_path))
    _spans.load.cache_clear()
    traced = {"trace": SimpleNamespace(step_busy_s={}),
              "record": {"steps": [], "decode_span": 4}, "tc": TC,
              "roofline": smallthinker_21ba3b_roofline,
              "device_kind": "TPU v5 lite",
              "cell": {"config_file": CONFIG_FILE}}
    assert _spans.of(traced) is not None
    assert all(_reader(name).read(traced) is None for name in NAMES)
    assert all(_reader(name).read({**traced, "trace": None}) is None
               for name in NAMES)
    bench = run.load_json(REPO, "BENCHMARK.json")
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NAMES)
    for name in NAMES:
        module = _reader(name)
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (module.LAYER, module.UNIT, module.MOVES) \
            == (entry["layer"], entry["unit"], entry["moves"])
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "tokens_per_s"
        assert entry["source"] == ("device_trace" if name.startswith("step.")
                                   else "program_span")
        assert entry["better"] == ("higher" if name.startswith("step.")
                                   else "lower")
