"""The small twin of ``configs/longcat-flash-chat.json``
(``configs/tiny_longcat.json``: the same three modules — the latent cache
row, the shortcut-connected expert layer with zero-compute experts, one
rank's share of the experts — at widths the CPU runs) through the whole
harness, as ``test_second_block.py`` takes ``tiny_moe``: entries in a copy
of ``BENCHMARK.json`` and files the entries' names point to.
``JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_longcat_twin.py
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

CONFIG = {"name": "tiny_longcat", "source": "none: chipbench/tests",
          "file": "chipbench/tests/configs/tiny_longcat.json", "reduced": [],
          "why": "latent cache row, shortcut expert layer, 4 of 16 experts"}
CELL = {"name": "tiny_longcat.rate", "config": "tiny_longcat",
        "traffic": "tiny.rate", "chips": 1,
        "why": "the latent block's twin under the tiny open loop"}
MODULES = {kind: f"chipbench.longcat_flash_{kind}" for kind in run.MODULES}


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
    cell = run.load_cell("lcf-ep32.gen.topics")
    assert cell["modules"] == MODULES and cell["chips"] == 1
    assert cell["mix"]["arrivals"] == "backlog"
    named = {m["name"] for m in cell["per_layer"]}
    assert {"moe.zero_share.backlog", "moe.held_rows_per_expert.backlog",
            "moe.held_touched_share.backlog",
            "step.mixed_routed_hbm_roofline.backlog",
            "step.mixed_hbm_roofline.backlog"} <= named
    assert all(os.path.isfile(os.path.join(cell["metric_dir"],
                                           f"{name}.py")) for name in named)
    tc = cell["config_file"]["transformer_config"]
    counts = run.cell_module(cell, "roofline")
    assert counts.kv_bytes_per_row(tc) == 9216
    assert counts.expert_bytes(tc) == 3 * 6144 * 2048 * 2
    # MLA 90.57 M and its two small norms, FFN 226.5 M, twice; 4 norms; router
    outside = (2 * (90_570_752 + 2048) + 2 * 226_492_416 + 4 * 6144
               + 6144 * 768)
    assert counts.layer_weight_count(tc) == outside
    assert counts.decode_step_weight_bytes(tc) == 2 * (
        4 * outside + 6144 + 6144 * 16384)


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
        assert session.engine.pool.k.shape[0] == 4  # a row a sub-layer
        low = dict(session.params)
        low["layers"] = [reference.lower_precision(layer, "fp8")
                         for layer in session.params["layers"]]
        low["lm_head"] = reference._LOW["fp8"](session.params["lm_head"])
        session.engine.params = low
        record = session.measure(3.0, cell["params"]["rate_rps"])
        assert not record["compiles_in_window"]
        assert session.engine.moe_passes > 0
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
