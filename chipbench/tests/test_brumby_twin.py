"""The small twin of ``configs/brumby-14b-base.json``
(``configs/tiny_brumby.json``: the same three modules — power-retention
layers over GQA 4 to 2 at head width 16 with per-head q/k norms and a log
gate a KV head, a dense SwiGLU — at widths the CPU runs) through the whole
harness, as ``test_sdar_twin.py`` takes ``tiny_sdar``: entries in a copy of
``BENCHMARK.json`` and files the entries' names point to.  The engine folds
at its real key block of 512 rows, so the twin's mix (``traffic/
tiny.folds.json``) sends prompts of 1,030-1,700 rows: every request folds
twice or three times while it prefills and reads its state from then on.
``JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_brumby_twin.py
-q``; each whole run starts the native token runtime."""

from __future__ import annotations

import json
import os
import shutil
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import run  # noqa: E402
from chipbench.tests.rehearse import fake_inventory  # noqa: E402

CONFIG = {"name": "tiny_brumby", "source": "none: chipbench/tests",
          "file": "chipbench/tests/configs/tiny_brumby.json", "reduced": [],
          "why": "2 power-retention layers of GQA 4 to 2 at head width 16, "
                 "a state a lane beside a paged tail"}
CELL = {"name": "tiny_brumby.rate", "config": "tiny_brumby",
        "traffic": "tiny.folds", "chips": 1,
        "why": "the retention block's twin under an open loop of prompts "
               "that cross the key block of 512 rows twice"}
MODULES = {kind: f"chipbench.brumby_14b_base_{kind}" for kind in run.MODULES}
NEW_METRICS = {"step.stage_ms.retention.backlog",
               "step.retention_hbm_roofline.backlog",
               "retention.state_bytes_share.backlog",
               "retention.tail_rows_per_lane.backlog"}


def _cell(tmp_path):
    copy = tmp_path / "BENCHMARK.json"
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), copy)
    bench = json.loads(copy.read_text())
    bench["configs"].append(CONFIG)
    bench["workloads"].append(CELL)
    for metric in bench["end_to_end"]:
        if metric["name"] in ("ttft_tail_ms", "token_gap_mean_ms"):
            metric["workloads"].append(CELL["name"])
    copy.write_text(json.dumps(bench))
    cell = run.load_cell(CELL["name"], json.loads(copy.read_text()), REPO)
    assert cell["modules"] == MODULES
    assert cell["params"]["rate_rps"] == 1.5 and cell["per_layer"] == []
    assert cell["mix"]["prompt"]["min"] > 2 * 512
    return cell


def test_the_cell_names_the_same_modules_as_its_twin():
    cell = run.load_cell("brumby-pp8.gen.topics")
    assert cell["modules"] == MODULES and cell["chips"] == 1
    assert cell["mix"] == run.load_cell("joyai-pp8.gen.topics")["mix"]
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["tokens_per_s", "setup_s"]
    named = {m["name"] for m in cell["per_layer"]}
    assert NEW_METRICS | {"step.mixed_device_ms.backlog",
                          "step.stage_ms.ffn.backlog",
                          "step.stage_unscoped_share.backlog",
                          "engine.host_ms_per_dispatch.backlog"} <= named
    # no expert, no kernel; and the harness's live rows are the requests'
    # whole lengths, which this block's pool does not hold
    assert not named & {"step.mixed_hbm_roofline.backlog",
                        "step.stage_ms.experts.backlog",
                        "step.attend_kernel_hbm_roofline.backlog"}
    assert all(os.path.isfile(os.path.join(cell["metric_dir"],
                                           f"{name}.py")) for name in named)
    config_file = cell["config_file"]
    tc, twin = config_file["transformer_config"], \
        run.load_json(HERE, "configs", "tiny_brumby.json")[
            "transformer_config"]
    assert set(tc) == set(twin)  # the twin runs every field the cell does
    for key in ("block", "positional", "rope_theta", "norm_eps", "dtype"):
        assert tc[key] == twin[key], key
    # the published widths, and the cut: depth alone
    assert (tc["d_model"], tc["n_heads"], tc["head_width"], tc["n_kv_heads"],
            tc["d_ff"], tc["vocab_size"], tc["n_layers"]) \
        == (5120, 40, 128, 8, 17408, 151936, 5)
    assert config_file["published"] == {"num_hidden_layers": 40}
    assert config_file["num_hidden_layers"] == tc["n_layers"]
    engine = config_file["engine"]
    assert (engine["num_slots"], engine["block_size"],
            engine["max_request_len"], engine["prefill_chunk"],
            engine["pool_bytes"]) == (32, 16, 8192, 512, 1 << 30)
    counts = run.cell_module(cell, "roofline")
    # what the pool holds a token: K, V and a float32 log gate a KV head
    assert counts.kv_bytes_per_row(tc) == 5 * 8 * (2 * 128 * 2 + 4) == 20640
    assert engine["pool_bytes"] // (20640 * 16) + 1 == 3252
    assert counts.phi_features(tc) == 8256 and counts.phi_width(tc) == 8320
    assert counts.state_bytes_per_lane(tc) == 5 * 8 * 136 * 8320 * 4
    assert counts.state_need_bytes_per_lane(tc) == 5 * 8 * 129 * 8320 * 4
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408 \
        + 5120 * 8 + 8 + 2 * 128 + 2 * 5120
    assert counts.layer_weight_count(tc) == layer == 330_352_904
    assert counts.decode_step_weight_bytes(tc) == 2 * (
        5 * layer + 5120 + 5120 * 151936)
    # with the embedding: what the chip holds of weights, and beside them
    assert abs((5 * layer + 2 * 5120 * 151936 + 5120) * 2 - 6.415e9) < 1e6
    assert abs(32 * counts.state_bytes_per_lane(tc) - 5.793e9) < 1e6
    reads = counts.retention_min_bytes(tc, 10, 1000, 2)
    assert reads == 10 * counts.state_need_bytes_per_lane(tc) \
        + 1000 * 20640 + 2 * (2 * counts.state_need_bytes_per_lane(tc)
                              + 512 * 20640)
    assert counts.decode_step_min_bytes(tc, 100, 3) \
        == counts.decode_step_weight_bytes(tc) + 100 * 20640 \
        + 3 * counts.state_need_bytes_per_lane(tc)
    correct = config_file["correct"]
    assert correct["sound_largest"] < correct["mean_gap_limit"] \
        < min(correct["control_smallest"], correct["tail_alone_smallest"])


def test_a_whole_window_of_the_twin_is_correct(tmp_path):
    cell = _cell(tmp_path)
    session = run.Session(cell, seed=2147484127, require_tpu=False,
                          inventory=fake_inventory())
    try:
        engine = session.engine
        assert engine.pool.gate.shape == (2, 2, engine.pool.num_blocks * 16)
        assert len(engine.states) == 2  # beside the pool, not in it
        record = session.measure(3.0, cell["params"]["rate_rps"])
        assert not record["compiles_in_window"]
        assert engine.retention_folds >= 2 * len(run.scored(record))
        assert engine.retention_state_reads > 0
        verdict = run.judge(session, record)
    finally:
        session.close()
    assert verdict["correct"] and verdict["failed"] == 0
    assert verdict["attempted"] == 4
    checks = {c["check"]: c for c in verdict["checks"]}
    assert checks["served_vs_reference.mean_gap"]["limit"] \
        == cell["config_file"]["correct"]["mean_gap_limit"]
    assert "served_vs_reference.widest_gap" not in checks


def test_the_twins_lower_precision_is_not_correct(tmp_path):
    """The control: the program serving an fp8 copy of the weights, the
    gate's map among them, and the reference's own fp8 pass."""
    cell = _cell(tmp_path)
    reference = run.cell_module(cell, "reference")
    session = run.Session(cell, seed=126, require_tpu=False,
                          inventory=fake_inventory())
    try:
        low = dict(session.params)
        low["layers"] = [reference.lower_precision(layer, "fp8")
                         for layer in session.params["layers"]]
        low["lm_head"] = reference._LOW["fp8"](session.params["lm_head"])
        session.engine.params = low
        record = session.measure(3.0, cell["params"]["rate_rps"])
        assert not record["compiles_in_window"]
        verdict = run.judge(session, record)
    finally:
        session.close()
    assert not verdict["correct"] and verdict["failed"] == 0
    failed = [c["check"] for c in verdict["checks"] if not c["ok"]]
    assert failed == ["served_vs_reference.mean_gap"]
    rng = np.random.default_rng(0)
    prompt, served = rng.integers(0, 512, 600), rng.integers(0, 512, 40)
    gaps = reference.summarize([reference.control_gaps(
        session.params, session.tc, prompt, served, "fp8")])
    assert gaps["mean_gap"] > cell["config_file"]["correct"]["mean_gap_limit"]


def test_the_twin_serving_from_the_tail_alone_is_not_correct(
        tmp_path, monkeypatch):
    """The other control: the same program with the state forgotten —
    every query answered from its lane's unfolded rows alone, as if the
    request began at its last fold.  A lane past its first key block loses
    most of its context, and ``mean_gap`` says so."""
    import jax.numpy as jnp

    from kubeshare_tpu.serving import paged

    def nothing_held(q, state, cum_q, has_state):
        return (jnp.zeros(q.shape, jnp.float32),
                jnp.zeros(q.shape[:3], jnp.float32))

    monkeypatch.setattr(paged, "state_sums", nothing_held)
    cell = _cell(tmp_path)
    session = run.Session(cell, seed=2147484127, require_tpu=False,
                          inventory=fake_inventory())
    try:
        record = session.measure(3.0, cell["params"]["rate_rps"])
        assert session.engine.retention_state_reads > 0  # planned, unread
        verdict = run.judge(session, record)
    finally:
        session.close()
    assert not verdict["correct"] and verdict["failed"] == 0
    failed = {c["check"]: c["value"] for c in verdict["checks"]
              if not c["ok"]}
    assert set(failed) == {"served_vs_reference.mean_gap"}
    assert failed["served_vs_reference.mean_gap"] \
        > cell["config_file"]["correct"]["tail_alone_smallest"] / 2
