"""The small twin of ``configs/smallthinker-21ba3b-instruct.json``
(``configs/tiny_smallthinker.json``: the same three modules — full attention
that rotates nothing in one layer of four, a 64-row window with rope in the
other three, GQA 14 to 2 (a query group of 7), a router on the layer's input
and 16 ReLU-gated experts top 6 — at widths the CPU runs, served from a cache
BY LAYER KIND) through the whole harness, as ``test_lfm2_twin.py`` takes
``tiny_lfm2``: entries in a copy of ``BENCHMARK.json`` and files the entries'
names point to.  Its traffic (``tiny.longmix``) crosses the window several
times a request, over a table of 64 entries, so the key-block loop runs under
a window and pages go back while requests run.  ``JAX_PLATFORMS=cpu python3
-m pytest chipbench/tests/test_smallthinker_twin.py -q``; each whole run
starts the native token runtime."""

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

CONFIG = {"name": "tiny_smallthinker", "source": "none: chipbench/tests",
          "file": "chipbench/tests/configs/tiny_smallthinker.json",
          "reduced": [],
          "why": "1 full layer without rotation and 3 under a 64-row window, "
                 "GQA 14 to 2, an early router, 16 ReLU experts top 6"}
CELL = {"name": "tiny_smallthinker.longmix", "config": "tiny_smallthinker",
        "traffic": "tiny.longmix", "chips": 1,
        "why": "the cache by layer kind's twin under a backlog of prompts "
               "of 1-9 windows"}
REAL = "smallthinker-pp7.gen.longmix"
MODULES = {kind: f"chipbench.smallthinker_21ba3b_{kind}"
           for kind in run.MODULES}
NEW_METRICS = {"step.mixed_kinds_routed_hbm_roofline.backlog",
               "step.attend_kinds_kernel_hbm_roofline.backlog",
               "kv.window_read_share.backlog",
               "kv.pool_bytes_per_context_row.backlog"}
LISTED = {"engine.host_ms_per_dispatch.backlog",
          "engine.schedule_ms_per_dispatch.backlog",
          "engine.marshal_ms_per_dispatch.backlog",
          "engine.fetch_ms_per_dispatch.backlog",
          "dispatch.gated_idle_ms.backlog", "step.mixed_device_ms.backlog",
          "step.stage_ms.attention.backlog", "step.stage_ms.kv_write.backlog",
          "step.stage_ms.experts.backlog", "step.stage_ms.head.backlog",
          "step.stage_unscoped_share.backlog",
          "step.experts_hbm_roofline.backlog",
          "moe.rows_per_touched_expert.backlog",
          "moe.tile_fill_share.backlog"}
# what counts a held row as read in EVERY layer: high under a window
NOT_LISTED = {"step.attend_kernel_hbm_roofline.backlog",
              "step.mixed_routed_hbm_roofline.backlog",
              "step.mixed_hbm_roofline.backlog",
              "step.mixed_expert_bytes_share.backlog"}


def _cell(tmp_path):
    copy = tmp_path / "BENCHMARK.json"
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), copy)
    bench = json.loads(copy.read_text())
    bench["configs"].append(CONFIG)
    bench["workloads"].append(CELL)
    for metric in bench["end_to_end"]:
        if metric["name"] == "tokens_per_s":
            metric["workloads"].append(CELL["name"])
    copy.write_text(json.dumps(bench))
    cell = run.load_cell(CELL["name"], json.loads(copy.read_text()), REPO)
    assert cell["modules"] == MODULES
    assert cell["params"] == {} and cell["per_layer"] == []
    assert cell["mix"]["arrivals"] == "backlog"
    return cell


def test_the_cell_names_the_same_modules_as_its_twin():
    cell = run.load_cell(REAL)
    assert cell["modules"] == MODULES and cell["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["tokens_per_s", "setup_s"]
    named = {m["name"] for m in cell["per_layer"]}
    assert named == NEW_METRICS | LISTED and not named & NOT_LISTED
    assert all(os.path.isfile(os.path.join(cell["metric_dir"],
                                           f"{name}.py")) for name in named)
    mix = cell["mix"]
    assert (mix["arrivals"], mix["backlog_block"], mix["queue_depth"],
            mix["max_total"], mix["temperature"], mix["drain_seconds"]) \
        == ("backlog", 64, 64, 16384, 0.0, 0)
    assert mix["prompt"] == {"dist": "lognormal", "median": 4096,
                             "sigma": 0.8, "min": 256, "max": 14336}
    assert mix["output"] == {"dist": "lognormal", "median": 768,
                             "sigma": 0.6, "min": 64, "max": 2048}
    assert not isinstance(mix["tokens"], dict)  # uniform over the vocabulary
    config_file = cell["config_file"]
    tc, twin = config_file["transformer_config"], \
        run.load_json(HERE, "configs", "tiny_smallthinker.json")[
            "transformer_config"]
    assert set(tc) == set(twin)  # the twin runs every field the cell does
    for key in ("block", "positional", "rope_theta", "norm_eps", "dtype",
                "router_scoring", "router_renormalise", "router_top_k",
                "routed_scaling_factor", "qk_norm", "router_input",
                "expert_activation", "d_ff"):
        assert tc[key] == twin[key], key
    assert twin["layer_operators"] == tc["layer_operators"][:4]
    assert tc["n_heads"] // tc["n_kv_heads"] \
        == twin["n_heads"] // twin["n_kv_heads"] == 7
    # the published widths, and the cut: depth alone
    assert (tc["d_model"], tc["n_heads"], tc["head_width"], tc["n_kv_heads"],
            tc["expert_d_ff"], tc["n_routed_experts"], tc["router_top_k"],
            tc["vocab_size"], tc["n_layers"], tc["attention_window"],
            tc["max_seq_len"], tc["rope_theta"]) \
        == (2560, 28, 128, 4, 768, 64, 6, 151936, 8, 4096, 16384, 1.5e6)
    assert config_file["published"] == {"num_hidden_layers": 52}
    assert config_file["num_hidden_layers"] == tc["n_layers"] == 8
    assert config_file["sliding_window_layout"] \
        == config_file["rope_layout"] == [0, 1, 1, 1] * 13
    assert tc["layer_operators"] == [
        "window" if near else "global"
        for near in config_file["sliding_window_layout"][:8]]
    assert (config_file["hidden_size"], config_file["head_dim"],
            config_file["moe_ffn_hidden_size"],
            config_file["moe_num_primary_experts"],
            config_file["moe_num_active_primary_experts"],
            config_file["sliding_window_size"], config_file["vocab_size"],
            config_file["max_position_embeddings"],
            config_file["rope_theta"], config_file["rms_norm_eps"],
            config_file["tie_word_embeddings"]) \
        == (2560, 128, 768, 64, 6, 4096, 151936, 16384, 1500000, 1e-6,
            False)
    entry = next(c for c in run.load_json(REPO, "BENCHMARK.json")["configs"]
                 if c["name"] == "smallthinker-21ba3b-instruct")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config_file["source"]
    engine = config_file["engine"]
    assert (engine["num_slots"], engine["block_size"],
            engine["max_request_len"], engine["prefill_chunk"],
            engine["pool_bytes"]) == (32, 16, 16384, 512, 1 << 32)
    assert config_file["pod"] == {"name": "serve-a", "gpu_request": 0.5,
                                  "gpu_limit": 1.0, "gpu_mem": 0.9}
    counts = run.cell_module(cell, "roofline")
    # what the pool holds a token: a K and a V a KV head of all 8 layers
    assert counts.kv_bytes_per_row(tc) == 8 * 2 * 4 * 128 * 2 == 16384
    assert engine["pool_bytes"] // (16384 * 16) + 1 == 16385
    assert counts.kv_read_bytes_by_kind(tc) == {"full": 4096,
                                                "window": 12288}
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert counts.attention_weight_count(tc) == attention == 20_971_520
    outside = 8 * (attention + 2 * 2560 + 2560 * 64)
    assert counts.outside_experts_count(tc) == outside
    assert counts.expert_bytes(tc) == 3 * 2560 * 768 * 2
    assert counts.decode_step_weight_bytes(tc) == 2 * (
        outside + 2560 + 2560 * 151936)
    assert counts.parameter_count(tc) == outside \
        + 8 * 64 * 3 * 2560 * 768 + 2560 + 2 * 2560 * 151936 \
        == 3_966_937_600
    # the floor counts the full kind's rows alone; by kind it is the
    # window's rows too, never more than every layer's
    weights = counts.decode_step_weight_bytes(tc)
    assert counts.decode_step_min_bytes(tc, 1000) == weights + 4096 * 1000
    assert counts.step_bytes_by_kind(tc, 10000, 4096) \
        == weights + 4096 * 10000 + 12288 * 4096 \
        < weights + 16384 * 10000
    correct = config_file["correct"]
    assert {"sample_requests", "mean_gap_limit", "readings", "controls"} \
        <= set(correct)
    if "sound_largest" in correct:  # read on the chip: the limit between
        assert correct["sound_largest"] < correct["mean_gap_limit"] \
            < correct["control_smallest"]
    twins = run.load_json(HERE, "configs", "tiny_smallthinker.json")["correct"]
    assert twins["sound_largest"] < twins["mean_gap_limit"] \
        < min(twins["control_smallest"], twins["every_row_smallest"],
              twins["rotated_global_smallest"])
    assert len(config_file["assumed"]) >= 7 \
        and len(config_file["departures"]) == 3


def _window(tmp_path, seed, tc_changes=None, low=False, seconds=4.0):
    """A whole window of the twin; ``tc_changes``: what the PROGRAM is
    built with in place of the file's fields (a control), the reference
    keeps the file's."""
    cell = _cell(tmp_path)
    told = cell["config_file"]["transformer_config"]
    if tc_changes:
        cell["config_file"] = {**cell["config_file"],
                               "transformer_config": {**told, **tc_changes}}
    session = run.Session(cell, seed=seed, require_tpu=False,
                          inventory=fake_inventory())
    try:
        session.tc = told  # what the reference is computed for
        engine = session.engine
        if low:
            reference = run.cell_module(cell, "reference")
            served = dict(session.params)
            served["layers"] = [reference.lower_precision(layer, "fp8")
                                for layer in session.params["layers"]]
            served["lm_head"] = reference._LOW["fp8"](
                session.params["lm_head"])
            engine.params = served
        record = session.measure(seconds, None)
        assert not record["compiles_in_window"]
        verdict = run.judge(session, record)
    finally:
        session.close()
    return cell, engine, record, verdict


def test_a_whole_window_of_the_twin_is_correct(tmp_path):
    cell, engine, record, verdict = _window(tmp_path, 2147484127)
    # a pool and a table a kind: 1 full layer, 3 window layers, bytes as
    # the count says
    assert [k.shape[0] for k in engine.pool.k] == [1, 3]
    assert engine.pool.k[0].shape[2:] == (2, 16, 16)
    assert engine._table_entries == 2 * engine._table_width == 128
    assert engine.prefix_index is None
    counted = engine.kv_kind_blocks
    # pages went back behind the window while requests ran
    assert counted["window", "released"] > counted["window", "reserved"] > 0
    assert counted["window", "reserved"] + counted["window", "drawn"] \
        >= counted["window", "released"] + counted["window", "returned"]
    assert verdict["correct"] and verdict["failed"] == 0
    assert verdict["attempted"] >= 8
    checks = {c["check"]: c for c in verdict["checks"]}
    assert checks["served_vs_reference.mean_gap"]["limit"] \
        == cell["config_file"]["correct"]["mean_gap_limit"]
    assert "served_vs_reference.widest_gap" not in checks


def _fails_by_the_mean_gap(verdict):
    assert not verdict["correct"] and verdict["failed"] == 0
    failed = [c["check"] for c in verdict["checks"] if not c["ok"]]
    assert failed == ["served_vs_reference.mean_gap"]


def test_the_twins_lower_precision_is_not_correct(tmp_path):
    """The control: the program serving an fp8 copy of the weights, and the
    reference's own fp8 pass."""
    cell, _, _, verdict = _window(tmp_path, 2147484127, low=True)
    _fails_by_the_mean_gap(verdict)
    reference = run.cell_module(cell, "reference")
    rng = np.random.default_rng(0)
    prompt, served = rng.integers(0, 512, 100), rng.integers(0, 512, 40)
    session_tc = cell["config_file"]["transformer_config"]
    params = run.cell_module(cell, "weights").make_weights(2147484127, session_tc)
    gaps = reference.summarize([reference.control_gaps(
        params, session_tc, prompt, served, "fp8")])
    assert gaps["mean_gap"] > cell["config_file"]["correct"]["mean_gap_limit"]


def test_the_twin_whose_window_layers_attend_every_row_is_not_correct(
        tmp_path):
    """A mechanism control: the same program with its window layers
    attending EVERY earlier row (a window no request reaches).  Requests
    longer than the window read rows the model does not see."""
    _, engine, _, verdict = _window(tmp_path, 2147484127,
                                    {"attention_window": 1 << 20})
    assert engine.kv_kind_blocks["window", "released"] == 0
    _fails_by_the_mean_gap(verdict)


def test_the_twin_that_rotates_in_its_full_layers_is_not_correct(tmp_path):
    """The other: the same program rotating q and k in the full layers too
    (the layer kind "attention" where the file says "global")."""
    _, _, _, verdict = _window(
        tmp_path, 2147484127,
        {"layer_operators": ["attention", "window", "window", "window"]})
    _fails_by_the_mean_gap(verdict)
