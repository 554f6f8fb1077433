"""A second block made of new files only: the repository's block with a
routed-expert feed-forward in every other layer (``configs/tiny_moe.json``),
whose weights ``chipbench/weights.py`` cannot make, whose step bytes follow
its routing and whose reference is its own.  It reaches the harness as a
later PR's configuration must: as entries in (a copy of) ``BENCHMARK.json``
and files the entries' names point to, with no file that was there edited.
``JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_second_block.py
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

CONFIG = {"name": "tiny_moe", "source": "none: chipbench/tests",
          "file": "chipbench/tests/configs/tiny_moe.json", "reduced": [],
          "why": "routed experts in every other layer, top 2 of 4"}
CELL = {"name": "tiny_moe.rate", "config": "tiny_moe", "traffic": "tiny.rate",
        "chips": 1, "why": "the second block under the tiny open loop"}


def _cell(tmp_path):
    """The cell, loaded as ``run.main`` loads one, from a copy of
    ``BENCHMARK.json`` with two entries added."""
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
    assert cell["modules"] == {
        kind: f"chipbench.tests.tiny_moe_{kind}" for kind in run.MODULES}
    assert cell["params"]["rate_rps"] == 6.0 and cell["per_layer"] == []
    return cell


def test_a_whole_window_of_the_second_block_is_correct(tmp_path):
    cell = _cell(tmp_path)
    result = run.run_cell(cell, seed=2147484127, seconds=3.0, trace=False,
                          require_tpu=False, inventory=fake_inventory())
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == 18  # 6 a second for 3 s: the scored
    assert set(result["metrics"]) == {"ttft_tail_ms", "token_gap_mean_ms",
                                      "setup_s"}
    assert list(result)[-1] == "checks"
    limits = cell["config_file"]["correct"]
    assert result["checks"]["served_vs_reference.mean_gap"]["limit"] \
        == limits["mean_gap_limit"]


def test_the_second_blocks_lower_precision_is_not_correct(tmp_path):
    """The control: the program serving an fp8 copy of the weights, the
    router among them, and the reference's own fp8 pass."""
    cell = _cell(tmp_path)
    reference = run.cell_module(cell, "reference")
    session = run.Session(cell, seed=126, require_tpu=False,
                          inventory=fake_inventory())
    try:
        assert "moe" in session.params["layers"][1]
        assert "mlp" in session.params["layers"][0]
        low = dict(session.params)
        low["layers"] = [reference.lower_precision(layer, "fp8")
                         for layer in session.params["layers"]]
        low["lm_head"] = reference.dense._LOW["fp8"](
            session.params["lm_head"])
        session.engine.params = low
        record = session.measure(3.0, cell["params"]["rate_rps"])
        assert not record["compiles_in_window"]
        verdict = run.judge(session, record)
    finally:
        session.close()
    assert not verdict["correct"] and verdict["failed"] == 0
    failed = [c["check"] for c in verdict["checks"] if not c["ok"]]
    assert failed and all(c.startswith("served_vs_reference") for c in failed)
    rng = np.random.default_rng(0)
    prompt, served = rng.integers(0, 512, 60), rng.integers(0, 512, 40)
    gaps = reference.summarize([reference.control_gaps(
        session.params, session.tc, prompt, served, "fp8")])
    assert gaps["mean_gap"] > cell["config_file"]["correct"]["mean_gap_limit"]
