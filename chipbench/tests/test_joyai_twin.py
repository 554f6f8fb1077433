"""The small twin of ``configs/joyai-llm-flash.json``
(``configs/tiny_joyai.json``: the same three modules — single latent layers
with an odd count of cache rows, a leading dense layer, the sigmoid router
with its choice bias over experts that are all held, the shared expert — at
widths the CPU runs) through the whole harness, as ``test_longcat_twin.py``
takes ``tiny_longcat``: entries in a copy of ``BENCHMARK.json`` and files the
entries' names point to.  ``JAX_PLATFORMS=cpu python3 -m pytest
chipbench/tests/test_joyai_twin.py -q``; each whole run starts the native
token runtime."""

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

CONFIG = {"name": "tiny_joyai", "source": "none: chipbench/tests",
          "file": "chipbench/tests/configs/tiny_joyai.json", "reduced": [],
          "why": "single latent layers, 1 dense + 2 routed, 16 experts held"}
CELL = {"name": "tiny_joyai.rate", "config": "tiny_joyai",
        "traffic": "tiny.rate", "chips": 1,
        "why": "the single-layer latent block's twin under the tiny open loop"}
MODULES = {kind: f"chipbench.joyai_llm_flash_{kind}" for kind in run.MODULES}


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
    cell = run.load_cell("joyai-pp8.gen.topics")
    assert cell["modules"] == MODULES and cell["chips"] == 1
    assert cell["mix"]["arrivals"] == "backlog"
    assert cell["config_file"]["engine"] == run.load_cell(
        "lcf-ep32.gen.topics")["config_file"]["engine"]
    named = {m["name"] for m in cell["per_layer"]}
    assert {"moe.rows_per_touched_expert.backlog",
            "moe.tile_fill_share.backlog",
            "step.mixed_expert_bytes_share.backlog",
            "step.mixed_routed_hbm_roofline.backlog",
            "step.mixed_hbm_roofline.backlog"} <= named
    # no zero-compute expert; and the two readers that multiply by every
    # layer would count the dense one
    assert not named & {"moe.zero_share.backlog",
                        "moe.held_rows_per_expert.backlog",
                        "moe.held_touched_share.backlog"}
    assert all(os.path.isfile(os.path.join(cell["metric_dir"],
                                           f"{name}.py")) for name in named)
    tc = cell["config_file"]["transformer_config"]
    counts = run.cell_module(cell, "roofline")
    # the pool's row: 5 latent rows, 3 rows of two rotary keys (half spare)
    assert counts.kv_bytes_per_row(tc) == (5 * 512 + 3 * 128) * 2 == 5888
    assert counts.kv_read_bytes_per_row(tc) == 5 * (512 + 64) * 2 == 5760
    assert counts.expert_bytes(tc) == 3 * 2048 * 768 * 2
    attention = 26_345_472 + 1536 + 512  # five matrices, two small norms
    assert counts.attention_weight_count(tc) == attention
    outside = (5 * (attention + 2 * 2048) + 3 * 2048 * 7168
               + 4 * (2048 * 256 + 256 + 3 * 2048 * 768))
    assert counts.outside_experts_count(tc) == outside
    assert counts.decode_step_weight_bytes(tc) == 2 * (
        outside + 2048 + 2048 * 129280)
    # with every expert and the embedding: what the chip holds
    held = outside + 4 * 256 * 3 * 2048 * 768 + 2 * 2048 * 129280 + 2048
    assert abs(held * 2 - 11.12e9) < 0.01e9


def test_a_whole_window_of_the_twin_is_correct(tmp_path):
    cell = _cell(tmp_path)
    result = run.run_cell(cell, seed=2147484127, seconds=3.0, trace=False,
                          require_tpu=False, inventory=fake_inventory())
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == 18
    limits = cell["config_file"]["correct"]
    assert set(result["checks"]) >= {"served_vs_reference.mean_gap"}
    assert "served_vs_reference.widest_gap" not in result["checks"]
    assert result["checks"]["served_vs_reference.mean_gap"]["limit"] \
        == limits["mean_gap_limit"]


def test_the_twins_lower_precision_is_not_correct(tmp_path):
    """The control: the program serving an fp8 copy of the weights, the
    router among them, and the reference's own fp8 pass."""
    cell = _cell(tmp_path)
    reference = run.cell_module(cell, "reference")
    session = run.Session(cell, seed=126, require_tpu=False,
                          inventory=fake_inventory())
    try:
        # a row a layer; three layers' rotary keys in two packed rows
        assert session.engine.pool.k.shape[0] == 3
        assert session.engine.pool.v.shape[0] == 2
        low = dict(session.params)
        low["layers"] = [reference.lower_precision(layer, "fp8")
                         for layer in session.params["layers"]]
        low["lm_head"] = reference._LOW["fp8"](session.params["lm_head"])
        session.engine.params = low
        record = session.measure(3.0, cell["params"]["rate_rps"])
        assert not record["compiles_in_window"]
        assert session.engine.moe_passes > 0
        assert session.engine.moe_assignments["zero"] == 0
        assert session.engine.moe_assignments["absent"] == 0
        assert session.engine.moe_tile_rows \
            >= session.engine.moe_assignments["held"] > 0
        verdict = run.judge(session, record)
    finally:
        session.close()
    assert not verdict["correct"] and verdict["failed"] == 0
    failed = [c["check"] for c in verdict["checks"] if not c["ok"]]
    assert failed == ["served_vs_reference.mean_gap"]
    rng = np.random.default_rng(0)
    prompt, served = rng.integers(0, 512, 60), rng.integers(0, 512, 40)
    gaps = reference.summarize([reference.control_gaps(
        session.params, session.tc, prompt, served, "fp8")])
    assert gaps["mean_gap"] > cell["config_file"]["correct"]["mean_gap_limit"]
