"""The small twin of ``configs/lfm2-24b-a2b.json`` (``configs/tiny_lfm2.json``:
the same three modules — gated short convolutions in three layers of four, GQA
4 to 2 at head width 64 with per-head q/k norms in the fourth, a leading dense
SwiGLU and 16 experts top 4 behind a sigmoid router with a choice bias — at
widths the CPU runs) through the whole harness, as ``test_brumby_twin.py``
takes ``tiny_brumby``: entries in a copy of ``BENCHMARK.json`` and files the
entries' names point to.  ``JAX_PLATFORMS=cpu python3 -m pytest
chipbench/tests/test_lfm2_twin.py -q``; each whole run starts the native token
runtime."""

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

CONFIG = {"name": "tiny_lfm2", "source": "none: chipbench/tests",
          "file": "chipbench/tests/configs/tiny_lfm2.json", "reduced": [],
          "why": "3 short convolutions and 1 GQA layer of 4 to 2 at head "
                 "width 64, a dense layer, then 16 experts top 4"}
CELL = {"name": "tiny_lfm2.rate", "config": "tiny_lfm2",
        "traffic": "tiny.rate", "chips": 1,
        "why": "the convolution-attention hybrid's twin under an open loop "
               "of prompts of 1-5 chunks"}
MODULES = {kind: f"chipbench.lfm2_24b_a2b_{kind}" for kind in run.MODULES}
NEW_METRICS = {"step.stage_ms.conv.backlog", "step.conv_roofline.backlog"}
LISTED = {"engine.host_ms_per_dispatch.backlog",
          "engine.schedule_ms_per_dispatch.backlog",
          "engine.marshal_ms_per_dispatch.backlog",
          "engine.fetch_ms_per_dispatch.backlog",
          "dispatch.gated_idle_ms.backlog", "step.mixed_device_ms.backlog",
          "step.stage_ms.attention.backlog", "step.stage_ms.kv_write.backlog",
          "step.stage_ms.ffn.backlog", "step.stage_ms.experts.backlog",
          "step.stage_ms.head.backlog", "step.stage_unscoped_share.backlog",
          "step.experts_hbm_roofline.backlog",
          "step.mixed_routed_hbm_roofline.backlog",
          "step.mixed_expert_bytes_share.backlog",
          "moe.rows_per_touched_expert.backlog",
          "moe.tile_fill_share.backlog",
          "step.attend_kernel_hbm_roofline.backlog"}


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
    assert cell["params"]["rate_rps"] == 6.0 and cell["per_layer"] == []
    return cell


def test_the_cell_names_the_same_modules_as_its_twin():
    cell = run.load_cell("lfm2-pp5.gen.topics")
    assert cell["modules"] == MODULES and cell["chips"] == 1
    assert cell["mix"] == run.load_cell("joyai-pp8.gen.topics")["mix"]
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["tokens_per_s", "setup_s"]
    named = {m["name"] for m in cell["per_layer"]}
    assert named == NEW_METRICS | LISTED
    # multiplied by n_layers, which counts the dense layers; no zero-compute
    # expert; the dense block's count of a step's bytes
    assert not named & {"moe.held_rows_per_expert.backlog",
                        "moe.held_touched_share.backlog",
                        "moe.zero_share.backlog",
                        "step.mixed_hbm_roofline.backlog"}
    assert all(os.path.isfile(os.path.join(cell["metric_dir"],
                                           f"{name}.py")) for name in named)
    config_file = cell["config_file"]
    tc, twin = config_file["transformer_config"], \
        run.load_json(HERE, "configs", "tiny_lfm2.json")[
            "transformer_config"]
    assert set(tc) == set(twin)  # the twin runs every field the cell does
    for key in ("block", "positional", "rope_theta", "norm_eps", "dtype",
                "head_width", "router_scoring", "router_choice_bias",
                "router_renormalise", "router_renormalise_eps", "conv_taps",
                "routed_scaling_factor"):
        assert tc[key] == twin[key], key
    assert twin["layer_operators"] == tc["layer_operators"][:4]
    # the published widths, and the cut: depth alone
    assert (tc["d_model"], tc["n_heads"], tc["head_width"], tc["n_kv_heads"],
            tc["d_ff"], tc["expert_d_ff"], tc["n_routed_experts"],
            tc["router_top_k"], tc["vocab_size"], tc["n_layers"],
            tc["first_dense_layers"]) \
        == (2048, 32, 64, 8, 11776, 1536, 64, 4, 65536, 8, 2)
    assert config_file["published"] == {"num_hidden_layers": 40}
    assert config_file["num_hidden_layers"] == tc["n_layers"] == 8
    assert len(config_file["layer_types"]) == 40
    assert tc["layer_operators"] == [
        "conv" if kind == "conv" else "attention"
        for kind in config_file["layer_types"][:8]]
    assert (config_file["hidden_size"], config_file["intermediate_size"],
            config_file["moe_intermediate_size"], config_file["num_experts"],
            config_file["num_experts_per_tok"], config_file["conv_L_cache"],
            config_file["num_dense_layers"], config_file["vocab_size"]) \
        == (2048, 11776, 1536, 64, 4, 3, 2, 65536)
    entry = next(c for c in run.load_json(REPO, "BENCHMARK.json")["configs"]
                 if c["name"] == "lfm2-24b-a2b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config_file["source"]
    engine = config_file["engine"]
    assert (engine["num_slots"], engine["block_size"],
            engine["max_request_len"], engine["prefill_chunk"],
            engine["pool_bytes"]) == (32, 16, 8192, 512, 1 << 30)
    counts = run.cell_module(cell, "roofline")
    # what the pool holds a token: a K and a V a KV head of the TWO
    # attention layers; the convolutions cache nothing a token
    assert counts.kv_bytes_per_row(tc) == 2 * 2 * 8 * 64 * 2 == 4096
    assert engine["pool_bytes"] // (4096 * 16) + 1 == 16385
    assert counts.state_bytes_per_lane(tc) == 6 * 2 * 2048 * 2
    conv = 2048 * 6144 + 2048 * 2048 + 2048 * 3
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert counts.conv_weight_count(tc) == conv == 16_783_360
    assert counts.attention_weight_count(tc) == attention == 10_485_888
    outside = 6 * conv + 2 * attention + 8 * 2 * 2048 \
        + 2 * 3 * 2048 * 11776 + 6 * (2048 * 64 + 64)
    assert counts.outside_experts_count(tc) == outside
    assert counts.decode_step_weight_bytes(tc) == 2 * (
        outside + 2048 + 2048 * 65536)
    assert counts.parameter_count(tc) == outside \
        + 6 * 64 * 3 * 2048 * 1536 + 2048 + 2 * 2048 * 65536 \
        == 4_159_511_168
    assert abs(counts.parameter_count(tc) * 2 - 8.319e9) < 1e6
    # the mechanism's roofline: a 512-row chunk is bound by its
    # multiply-adds, a pass of 31 lanes by the six operators' bytes
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    chunk = counts.conv_min_seconds(tc, peaks, 1, 0, 512)
    assert chunk == counts.conv_pass_flops(tc, 512) / 197e12
    assert chunk > counts.conv_pass_bytes(tc, 1) / 819e9
    span = counts.conv_min_seconds(tc, peaks, 31, 4, 0)
    assert span == 4 * counts.conv_pass_bytes(tc, 31) / 819e9
    assert counts.conv_min_seconds(tc, peaks, 32, 4, 512) \
        == span + chunk
    correct = config_file["correct"]
    assert correct["sound_largest"] < correct["mean_gap_limit"] \
        < min(correct["control_smallest"], correct["zeroed_state_smallest"])


def test_a_whole_window_of_the_twin_is_correct(tmp_path):
    cell = _cell(tmp_path)
    session = run.Session(cell, seed=2147484127, require_tpu=False,
                          inventory=fake_inventory())
    try:
        engine = session.engine
        assert engine.pool.k.shape[0] == 1  # the attention layer's alone
        assert engine.pool.k.shape[2:] == (1, 16, 128)  # two heads a row
        assert [s.shape for s in engine.states] == [(4, 2, 64)] * 3
        assert engine.prefix_index is None
        record = session.measure(3.0, cell["params"]["rate_rps"])
        assert not record["compiles_in_window"]
        assert engine.conv_state_resets >= len(run.scored(record))
        assert engine.conv_state_reads > 0
        verdict = run.judge(session, record)
    finally:
        session.close()
    assert verdict["correct"] and verdict["failed"] == 0
    assert verdict["attempted"] == 18
    checks = {c["check"]: c for c in verdict["checks"]}
    assert checks["served_vs_reference.mean_gap"]["limit"] \
        == cell["config_file"]["correct"]["mean_gap_limit"]
    assert "served_vs_reference.widest_gap" not in checks


def test_the_twins_lower_precision_is_not_correct(tmp_path):
    """The control: the program serving an fp8 copy of the weights, the
    filters among them, and the reference's own fp8 pass."""
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
    prompt, served = rng.integers(0, 512, 100), rng.integers(0, 512, 40)
    gaps = reference.summarize([reference.control_gaps(
        session.params, session.tc, prompt, served, "fp8")])
    assert gaps["mean_gap"] > cell["config_file"]["correct"]["mean_gap_limit"]


def test_the_twin_with_its_state_zeroed_is_not_correct(tmp_path,
                                                       monkeypatch):
    """The other control: the same program with the convolutions' states
    zeroed at every dispatch — what a lost or stale state would serve.
    Every chunk past a prompt's first and every decode span begins as if its
    lane's rows began there, and ``mean_gap`` says so."""
    import jax

    from kubeshare_tpu.serving.engine import ServingEngine

    carried = ServingEngine._recurrent_args

    def forgotten(self, p_slot, decode_slots):
        self.states = jax.tree.map(lambda s: s * 0, self.states)
        return carried(self, p_slot, decode_slots)

    monkeypatch.setattr(ServingEngine, "_recurrent_args", forgotten)
    cell = _cell(tmp_path)
    session = run.Session(cell, seed=2147484127, require_tpu=False,
                          inventory=fake_inventory())
    try:
        record = session.measure(3.0, cell["params"]["rate_rps"])
        assert session.engine.conv_state_reads > 0  # planned, read as zeros
        verdict = run.judge(session, record)
    finally:
        session.close()
    assert not verdict["correct"] and verdict["failed"] == 0
    failed = {c["check"]: c["value"] for c in verdict["checks"]
              if not c["ok"]}
    assert set(failed) == {"served_vs_reference.mean_gap"}
