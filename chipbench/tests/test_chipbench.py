"""The benchmark's own tests.  Run by hand: ``JAX_PLATFORMS=cpu python3 -m
pytest chipbench/tests -q`` (they are not part of tier-1).  The three that
drive a whole run start the native token runtime and take about ten
seconds each."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import metrics, roofline, run, trace, traffic  # noqa: E402
from chipbench.tests.rehearse import fake_inventory, tiny_cell  # noqa: E402

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
FIXTURE = os.path.join(HERE, "data", "tiny.xplane.pb")


# -- traffic ----------------------------------------------------------------

def _first(stream, n):
    return [next(stream) for _ in range(n)]


@pytest.mark.parametrize("mix_name", ["gen.rate", "gen.shared"])
def test_generator_same_seed_same_requests(mix_name):
    mix = traffic.load_mix(mix_name)
    a, b, c = (_first(traffic.requests(mix, 1.6, 50, 49152, seed), 100)
               for seed in (2147484001, 2147484001, 7))
    assert all((x.prompt == y.prompt).all() and x.due == y.due
               and x.max_new == y.max_new for x, y in zip(a, b))
    # another seed: the same schedule (lengths, pairing, arrivals), other
    # tokens
    assert all(len(x.prompt) == len(y.prompt) and x.max_new == y.max_new
               and x.due == y.due for x, y in zip(a, c))
    assert not any((x.prompt == y.prompt).all() for x, y in zip(a, c))
    # 80 are due inside the window: the stratified quantiles of the mix,
    # each once; the schedule then goes on, 50 s later, unscored
    own = [x for x in a if x.due < 50]
    assert len(own) == 80 and a[:80] == own
    assert sorted(len(x.prompt) for x in own) == list(
        traffic.lognormal_lengths(mix["prompt"], 80))
    assert [x.due for x in a] == sorted(x.due for x in a)
    assert 0.0 == a[0].due and a[80].due == 50.0
    assert [len(x.prompt) for x in a[80:]] == [len(x.prompt) for x in a[:20]]
    assert len({x.rid for x in a}) == 100
    assert all(len(x.prompt) + x.max_new <= mix["max_total"] for x in a)
    assert all(64 <= len(x.prompt) <= 3072 and 16 <= x.max_new <= 512
               for x in a)


@pytest.mark.parametrize("rate", [1.6, 2.0, 2.5, 3.125, 3.9])
def test_no_five_seconds_offer_much_more_than_another(rate):
    """The even order: at every rate of a sweep, each 5 s of the window
    is offered within four tenths of the mean in requests and a half in
    prefill chunks and output tokens (a random order of the same 80
    requests put 17 of them, 68 of 253 chunks, into the last 5 s)."""
    mix = traffic.load_mix("gen.rate")
    n = round(rate * 50)
    own = _first(traffic.requests(mix, rate, 50, 49152, 3), n)
    slot = np.array([int(x.due // 5) for x in own])
    assert slot.max() == 9
    for weight, room in (
            (np.ones(n), 0.4),
            (np.array([-(-len(x.prompt) // 256) for x in own]), 0.5),
            (np.array([x.max_new for x in own]), 0.5)):
        load = np.bincount(slot, weights=weight, minlength=10)
        assert abs(load / load.mean() - 1).max() <= room, load
    lengths = np.array([[len(x.prompt), x.max_new] for x in own])
    assert abs(np.corrcoef(lengths.T)[0, 1]) < 0.2


def test_unknown_arrivals_are_refused(tmp_path):
    mix = dict(traffic.load_mix("gen.rate"), arrivals="gamma")
    (tmp_path / "odd.json").write_text(json.dumps(mix))
    with pytest.raises(ValueError, match="arrivals"):
        traffic.load_mix("odd", str(tmp_path))
    with pytest.raises(ValueError, match="rate_rps"):
        traffic.requests(traffic.load_mix("gen.rate"), None, 50, 512, 1)


def test_backlog_is_lazy_and_seeded():
    mix = traffic.load_mix("gen.backlog")
    a, b = (traffic.requests(mix, None, 50, 49152, 9) for _ in range(2))
    first = [next(a) for _ in range(150)]  # crosses two block boundaries
    again = [next(b) for _ in range(150)]
    assert all((x.prompt == y.prompt).all() for x, y in zip(first, again))
    assert len({x.rid for x in first}) == 150
    assert all(x.due == 0.0 for x in first)


def test_a_token_law_is_data(tmp_path):
    """``"tokens"`` as an object: ids by Zipf rank under one of ``topics``
    seeded orders of the vocabulary.  The same seed gives the same ids; the
    hottest 1% of a topic's ranks carry the share the exponent says; two
    topics have other hot ids; the lengths and arrivals are untouched."""
    vocab, s, law = 4096, 1.2, {"dist": "zipf", "exponent": 1.2, "topics": 2}
    plain = traffic.load_mix("gen.rate")
    (tmp_path / "zipf.json").write_text(json.dumps(dict(plain, tokens=law)))
    mix = traffic.load_mix("zipf", str(tmp_path))
    a, b, u = (_first(traffic.requests(m, 1.6, 50, vocab, seed), 80)
               for m, seed in ((mix, 9), (mix, 9), (plain, 9)))
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert all(len(x.prompt) == len(y.prompt) and x.due == y.due
               and x.max_new == y.max_new for x, y in zip(a, u))
    assert all(x.prompt.dtype == np.int32 and 0 <= x.prompt.min()
               and x.prompt.max() < vocab for x in a)
    # a request's topic shows in its most frequent id: two topics, two ids
    hottest = [np.bincount(x.prompt, minlength=vocab).argmax() for x in a
               if len(x.prompt) >= 256]
    assert len(set(hottest)) == 2
    weights = np.arange(1, vocab + 1) ** -s
    said = weights[:vocab // 100].sum() / weights.sum()
    for top in set(hottest):
        ids = np.concatenate([x.prompt for x in a if len(x.prompt) >= 256
                              and np.bincount(x.prompt).argmax() == top])
        counts = np.sort(np.bincount(ids, minlength=vocab))[::-1]
        share = counts[:vocab // 100].sum() / counts.sum()
        assert abs(share - said) < 0.03, (share, said)
    flat = np.concatenate([x.prompt for x in u])
    assert np.sort(np.bincount(flat, minlength=vocab))[::-1][
        :vocab // 100].sum() / flat.size < 0.03  # uniform: about 1%
    (tmp_path / "odd.json").write_text(json.dumps(dict(
        plain, tokens={"dist": "pareto"})))
    with pytest.raises(ValueError, match="token law"):
        traffic.load_mix("odd", str(tmp_path))


# -- end-to-end arithmetic --------------------------------------------------

def test_percentile_matches_numpy_and_hand():
    values = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert metrics.percentile(values, 50) == 3.0
    assert metrics.percentile(values, 90) == pytest.approx(4.6)
    rng = np.random.default_rng(0).normal(size=101)
    assert metrics.percentile(list(rng), 90) == pytest.approx(
        np.percentile(rng, 90))


def test_slowest_tenth_is_a_mean_over_whole_requests():
    values = list(range(1, 81))  # 80 requests: the slowest eight
    assert metrics.slowest_tenth_mean(values) == sum(range(73, 81)) / 8
    assert metrics.slowest_tenth_mean([3.0, 1.0, 2.0]) == 3.0  # 3 -> one
    assert metrics.slowest_tenth_mean(list(range(11))) == 9.5  # 11 -> two


def test_times_are_taken_from_the_due_instant():
    opened = 1000.0  # the window opened at monotonic 1000
    # due at 2.0, sent late at 2.3, first token at 3.1: 1.1 s, not 0.8
    assert metrics.ttft_seconds(opened, 2.0, 1003.1, 50.0) == pytest.approx(1.1)
    # no first token: the window's length
    assert metrics.ttft_seconds(opened, 2.0, None, 50.0) == 50.0
    # 11 tokens from 1003.1 to 1003.6: ten gaps of 50 ms
    assert metrics.token_gap_seconds(1003.1, 1003.6, 11) == pytest.approx(0.05)
    assert metrics.token_gap_seconds(1003.1, 1003.6, 1) is None
    assert metrics.token_gap_seconds(1003.1, None, 5) is None


def test_a_long_step_is_named_and_a_short_one_is_not(monkeypatch):
    import time

    monkeypatch.setattr(run.StallWatch, "LONG_S", 0.4)
    watch = run.StallWatch()
    try:
        watch.begin()
        watch.end(0.0, 0, "decode")
        watch.begin()
        time.sleep(0.9)
        watch.end(1.5, 1, "mixed")
    finally:
        watch.close()
    (long,) = watch.long_steps
    assert (long["i"], long["kind"], long["at_s"]) == (1, "mixed", 1.5)
    assert 0.9 <= long["wall_s"] < 2.0 and long["thread_cpu_s"] < 0.3
    assert set(long["host_cpu_s"]) >= {"user", "idle", "steal"}
    assert any("test_a_long_step" in frame
               for frame in long["stacks"]["MainThread"])


# -- trace reduction --------------------------------------------------------

def test_interval_arithmetic():
    assert trace.merge([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    covered = trace.Covered([(0, 2), (3, 4), (1, 1.5)])
    assert covered.within(0, 10) == 3
    assert covered.within(1, 3.5) == 1.5
    assert covered.within(2, 3) == 0
    # a while holds its body: each instant is counted once
    times = trace.self_times([(0, 10, "while"), (1, 4, "fusion"),
                              (5, 9, "fusion"), (12, 13, "copy")])
    assert times == {"while": 3, "fusion": 7, "copy": 1}
    assert trace.short_name(
        "%copy.139 = bf16[2,65,2,16,16]{4,2:T(2,128)} copy(bf16[2] %x)"
    ) == "copy bf16[2,65,2,16,16]"
    assert trace.short_name("%while.5 = (s32[], bf16[2]) while(%t)") \
        == "while (tuple)"


def test_reduction_of_the_recorded_trace():
    """``data/tiny.xplane.pb``: the tiny configuration on a TPU v5e, 0.2 s
    traced (chipbench/tools/record_fixture.py, builder's chip run, PR 23)."""
    s = trace.reduce_trace(FIXTURE)
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.20779311, rel=1e-6)
    assert s.busy_s == pytest.approx(0.00108799, rel=1e-4)
    assert 0 < s.busy_s < s.window_s
    idle = dict((n, t) for n, t in s.idle_gaps)
    assert sum(idle.values()) + s.busy_s == pytest.approx(s.window_s, rel=1e-9)
    assert max(idle, key=idle.get) == "sleep-until-due"
    assert set(idle) <= {"sleep-until-due", "engine.step", "guard.acquire",
                         "submit", "unannotated"}
    assert len(s.device_ops) <= 10
    assert s.device_ops == sorted(s.device_ops, key=lambda kv: -kv[1])
    assert sum(t for _, t in s.device_ops) <= s.busy_s * (1 + 1e-9)
    assert s.step_busy_s and set(s.step_busy_s) == set(s.step_wall_s)
    assert all(s.step_busy_s[i] <= s.step_wall_s[i] for i in s.step_busy_s)
    assert sum(s.step_busy_s.values()) <= s.busy_s * (1 + 1e-9)


# -- operations and bytes ---------------------------------------------------

def _tc(name):
    return json.load(open(os.path.join(
        REPO, "chipbench", "configs", f"{name}.json")))["transformer_config"]


def test_roofline_bytes_against_hand_counts():
    one = _tc("starcoderbase-1b")
    # per layer: wq, wo 2048x2048 each; wk, wv 2048x128 each (MQA);
    # w_in, w_out 2048x8192 each; two norm scales
    layer = 2 * 2048 * 2048 + 2 * 2048 * 128 + 2 * 2048 * 8192 + 2 * 2048
    assert roofline.layer_weight_count(one) == layer == 42_471_424
    assert roofline.decode_step_weight_bytes(one) == \
        2 * (24 * layer + 2048 + 2048 * 49152) == 2_239_959_040
    assert roofline.kv_bytes_per_row(one) == 2 * 24 * 1 * 128 * 2 == 12288
    three = _tc("starcoder2-3b")
    layer3 = 2 * 3072 * 3072 + 2 * 3072 * 2 * 128 + 2 * 3072 * 12288 + 2 * 3072
    assert roofline.layer_weight_count(three) == layer3
    assert roofline.decode_step_weight_bytes(three) == \
        2 * (30 * layer3 + 3072 + 3072 * 49152) == 6_059_046_912
    assert roofline.kv_bytes_per_row(three) == 2 * 30 * 2 * 128 * 2 == 30720
    assert roofline.decode_step_min_bytes(three, 1000) == \
        6_059_046_912 + 30_720_000
    assert roofline.matmul_chain_flops(4096, 8) == pytest.approx(1.0995e12,
                                                                 rel=1e-4)
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")


# -- the files a cell is made of --------------------------------------------

def test_every_cell_and_metric_has_its_files():
    for cell in BENCH["workloads"]:
        loaded = run.load_cell(cell["name"], BENCH)
        assert {m["name"] for m in loaded["end_to_end"]} >= {"setup_s"}
        assert len(loaded["end_to_end"]) >= 2 and loaded["per_layer"]
        if loaded["mix"]["arrivals"] == "exponential":
            assert loaded["params"]["rate_rps"] > 0
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for metric in BENCH["per_layer"]:
        module = run.load_reader(os.path.join(REPO, "chipbench",
                                              "layer_metrics"), metric["name"])
        assert (module.LAYER, module.UNIT, module.MOVES) == \
            (metric["layer"], metric["unit"], metric["moves"])
        reporting = e2e[metric["moves"]].get(
            "workloads", [w["name"] for w in BENCH["workloads"]])
        assert set(metric["workloads"]) <= set(reporting)


# -- whole runs at a tiny width, on the CPU ---------------------------------

def _session(kind="rate"):
    return run.Session(tiny_cell(kind), seed=126, require_tpu=False,
                       inventory=fake_inventory())


def _verdict(session, seconds=3.0):
    try:
        record = session.measure(seconds, 6.0)
        assert not record["compiles_in_window"]
        return run.judge(session, record)
    finally:
        session.close()


def test_program_agrees_with_the_plain_reference():
    verdict = _verdict(_session())
    assert verdict["correct"] and verdict["failed"] == 0
    assert verdict["attempted"] == 18  # 6 a second for 3 s: the scored


def test_lower_precision_fails_the_tolerance():
    """The control, at the test's size: the program serving an fp8 copy of
    the weights (and the reference its own fp8 pass) is not correct."""
    from chipbench import reference

    session = _session()
    low = dict(session.params)
    low["layers"] = [reference.lower_precision(layer, "fp8")
                     for layer in session.params["layers"]]
    low["lm_head"] = reference._LOW["fp8"](session.params["lm_head"])
    session.engine.params = low
    verdict = _verdict(session)
    assert not verdict["correct"]
    failed = [c["check"] for c in verdict["checks"] if not c["ok"]]
    assert failed and all(c.startswith("served_vs_reference") for c in failed)
    # and the reference-side control, as the chip's readings take it
    tc = tiny_cell("rate")["config_file"]
    rng = np.random.default_rng(0)
    prompt, served = rng.integers(0, 512, 60), rng.integers(0, 512, 40)
    gaps = reference.summarize([reference.control_gaps(
        session.params, tc["transformer_config"], prompt, served, "fp8")])
    assert gaps["mean_gap"] > tc["correct"]["mean_gap_limit"]


def test_broken_timed_path_is_not_correct():
    """Everything of a run but the look for a chip, with the decode program
    returning other tokens than it computed."""
    session = _session()
    sound = session.engine._decode_step

    def altered(*args):
        emitted, pool_k, pool_v = sound(*args)
        # altered on the host: an eager jnp op would compile in the window
        return (np.asarray(emitted) + 1) % 512, pool_k, pool_v

    altered._cache_size = sound._cache_size  # the zero-recompile check's
    session.engine._decode_step = altered
    verdict = _verdict(session)
    assert not verdict["correct"]
    assert verdict["failed"] == 0  # every request got its tokens: wrong ones


def test_sweep_tool_finds_a_knee_and_reads_the_limits(monkeypatch, capsys):
    """``tools/sweep.py`` end to end at the tiny size: a knee search, then
    windows with the reference's and the controls' readings."""
    from chipbench.tools import sweep

    monkeypatch.setattr(run, "load_cell", lambda name: tiny_cell("rate"))
    session = _session()
    monkeypatch.setattr(run, "Session", lambda cell, seed: session)
    monkeypatch.setattr(run, "REPO", os.environ.get("TMPDIR", "/tmp"))
    sweep.main(["--workload", "tiny.rate", "--start", "4", "--max-sweep",
                "2", "--seconds", "3", "--windows", "5,6", "--controls",
                "1"])
    lines = [json.loads(text) for text in capsys.readouterr().out.split("\n")
             if text.startswith("{")]
    sweeps = [x["sweep"] for x in lines if "sweep" in x]
    windows = [x["window"] for x in lines if "window" in x]
    knee = next(x for x in lines if "knee_rps" in x)
    assert len(sweeps) == 2 and len(windows) == 2
    assert knee["rate_rps"] == pytest.approx(knee["knee_rps"] / 1.25)
    assert all(w["rate_rps"] == knee["rate_rps"] for w in windows)
    assert [w["seed"] for w in windows] == [5, 6]
    assert all(w["scored"] == w["served_in_full"] for w in windows)
    assert "control_fp8" in windows[0] and "control_fp8" not in windows[1]
    assert windows[0]["control_fp8"]["mean_gap"] > \
        windows[0]["program"]["mean_gap"]
    assert {"ttft_tail_ms", "token_gap_mean_ms"} <= set(windows[0]["metrics"])
    assert {"ttft_p90_ms", "token_gap_p90_ms"} <= set(windows[0])


def test_unknown_workload_and_cpu_are_refused():
    with pytest.raises(SystemExit):
        run.load_cell("no.such.cell", BENCH)
    with pytest.raises(SystemExit, match="needs a TPU"):
        run.Session(run.load_cell("scb-1b.gen.rate", BENCH), seed=1)
