"""The small twin of ``configs/sdar-30b-a3b-chat.json``
(``configs/tiny_sdar.json``: the same three modules — K/V-a-head attention at
an explicit head width with per-head q/k norms, the routed experts as every
layer's feed-forward, generation by diffusion over blocks of 4 in 4 steps —
at widths the CPU runs) through the whole harness, as ``test_joyai_twin.py``
takes ``tiny_joyai``: entries in a copy of ``BENCHMARK.json`` and files the
entries' names point to.  ``JAX_PLATFORMS=cpu python3 -m pytest
chipbench/tests/test_sdar_twin.py -q``; each whole run starts the native
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

CONFIG = {"name": "tiny_sdar", "source": "none: chipbench/tests",
          "file": "chipbench/tests/configs/tiny_sdar.json", "reduced": [],
          "why": "3 layers of GQA 4 to 2 at head width 32, 16 experts held, "
                 "diffusion over blocks of 4"}
CELL = {"name": "tiny_sdar.rate", "config": "tiny_sdar",
        "traffic": "tiny.rate", "chips": 1,
        "why": "the diffusion block's twin under the tiny open loop"}
MODULES = {kind: f"chipbench.sdar_30b_a3b_chat_{kind}" for kind in run.MODULES}
NEW_METRICS = {"diffusion.rows_per_token.backlog",
               "diffusion.passes_per_block.backlog",
               "step.diffusion_device_ms.backlog",
               "step.diffusion_routed_hbm_roofline.backlog",
               "step.mixed_diffusion_routed_hbm_roofline.backlog"}


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
    cell = run.load_cell("sdar-pp8.gen.topics")
    assert cell["modules"] == MODULES and cell["chips"] == 1
    assert cell["mix"]["arrivals"] == "backlog"
    assert cell["mix"] == run.load_cell("joyai-pp8.gen.topics")["mix"]
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["tokens_per_s", "setup_s"]
    named = {m["name"] for m in cell["per_layer"]}
    assert NEW_METRICS | {"step.mixed_device_ms.backlog",
                          "moe.rows_per_touched_expert.backlog",
                          "moe.tile_fill_share.backlog",
                          "moe.held_rows_per_expert.backlog",
                          "moe.held_touched_share.backlog"} <= named
    # what assumes a decode span's weight passes a dispatch, or that every
    # dispatch of a backlog is mixed, does not hold here
    assert not named & {"step.mixed_hbm_roofline.backlog",
                        "step.mixed_routed_hbm_roofline.backlog",
                        "step.mixed_expert_bytes_share.backlog",
                        "moe.zero_share.backlog"}
    assert all(os.path.isfile(os.path.join(cell["metric_dir"],
                                           f"{name}.py")) for name in named)
    config_file = cell["config_file"]
    tc, twin = config_file["transformer_config"], \
        run.load_json(HERE, "configs", "tiny_sdar.json")["transformer_config"]
    assert set(tc) == set(twin)  # the twin runs every field the cell does
    for key in ("block", "router_scoring", "router_renormalise",
                "diffusion_block", "diffusion_steps", "rope_theta"):
        assert tc[key] == twin[key], key
    # the published widths, and the cut: depth alone
    assert (tc["d_model"], tc["n_heads"], tc["head_width"], tc["n_kv_heads"],
            tc["n_routed_experts"], tc["expert_d_ff"], tc["router_top_k"],
            tc["vocab_size"], tc["n_layers"]) \
        == (2048, 32, 128, 4, 128, 768, 8, 151936, 6)
    assert config_file["published"] == {"num_hidden_layers": 48}
    assert config_file["num_hidden_layers"] == tc["n_layers"]
    counts = run.cell_module(cell, "roofline")
    assert counts.kv_bytes_per_row(tc) == 6 * 2 * 4 * 128 * 2 == 12288
    assert config_file["engine"]["pool_bytes"] // (12288 * 16) + 1 == 16385
    assert counts.expert_bytes(tc) == 3 * 2048 * 768 * 2
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    assert counts.attention_weight_count(tc) == attention == 18_874_624
    outside = 6 * (attention + 2 * 2048 + 2048 * 128)
    assert counts.outside_experts_count(tc) == outside
    assert counts.decode_step_weight_bytes(tc) == 2 * (
        outside + 2048 + 2048 * 151936)
    assert counts.pass_min_bytes(tc, 1000, 700) == \
        counts.decode_step_min_bytes(tc, 1000) + 700 * counts.expert_bytes(tc)
    # with every expert and the embedding: what the chip holds
    held = outside + 6 * 128 * 3 * 2048 * 768 + 2 * 2048 * 151936 + 2048
    assert abs(held * 2 - 8.72e9) < 0.01e9
    limit = config_file["correct"]["mean_gap_limit"]
    assert config_file["correct"]["sound_largest"] < limit \
        < config_file["correct"]["control_smallest"]
    # and the commit order's: the sound runs' largest, the index-order
    # program's smallest
    assert config_file["correct"]["order_sound_largest"] \
        < config_file["correct"]["order_gap_limit"] \
        < config_file["correct"]["order_control_smallest"]


def test_a_whole_window_of_the_twin_is_correct(tmp_path):
    cell = _cell(tmp_path)
    result = run.run_cell(cell, seed=2147484127, seconds=3.0, trace=False,
                          require_tpu=False, inventory=fake_inventory())
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == 18
    limits = cell["config_file"]["correct"]
    assert set(result["checks"]) >= {"served_vs_reference.mean_gap",
                                     "served_vs_reference.order_gap"}
    assert result["checks"]["served_vs_reference.order_gap"]["value"] < 0
    assert "served_vs_reference.widest_gap" not in result["checks"]
    assert result["checks"]["served_vs_reference.mean_gap"]["limit"] \
        == limits["mean_gap_limit"]


class _ByIndex:
    """A step program whose passes commit the lowest open rows instead of
    the most confident: the same picks, another order."""

    def __init__(self, step):
        self.step = step

    def __getattr__(self, name):
        return getattr(self.step, name)

    def __call__(self, *args):
        import jax.numpy as jnp

        picked, _, *rest = self.step(*args)
        active, open_rows, quota = args[-5], args[-2], args[-1]
        may = open_rows & active[:, None]
        commit = may & (jnp.cumsum(may, 1) <= quota[:, None])
        return (picked, commit, *rest)


def test_the_twin_committing_in_index_order_is_not_correct(tmp_path):
    """The other control: the same program, every pass committing its
    block's lowest open rows.  Its tokens fit the schedule it ran better
    than the stated one, and ``order_gap`` says so."""
    cell = _cell(tmp_path)
    session = run.Session(cell, seed=2147484127, require_tpu=False,
                          inventory=fake_inventory())
    try:
        engine = session.engine
        engine._diffusion_step = _ByIndex(engine._diffusion_step)
        engine._mixed_diffusion_step = _ByIndex(engine._mixed_diffusion_step)
        record = session.measure(3.0, cell["params"]["rate_rps"])
        verdict = run.judge(session, record)
    finally:
        session.close()
    assert not verdict["correct"] and verdict["failed"] == 0
    failed = {c["check"]: c["value"] for c in verdict["checks"]
              if not c["ok"]}
    assert "served_vs_reference.order_gap" in failed
    assert failed["served_vs_reference.order_gap"] > 0


def test_the_twins_lower_precision_is_not_correct(tmp_path):
    """The control: the program serving an fp8 copy of the weights, the
    router among them, and the reference's own fp8 pass."""
    cell = _cell(tmp_path)
    reference = run.cell_module(cell, "reference")
    session = run.Session(cell, seed=126, require_tpu=False,
                          inventory=fake_inventory())
    try:
        assert session.engine.pool.k.shape[0] == 3  # a K and a V a layer
        assert session.engine.pool.v.shape == session.engine.pool.k.shape
        low = dict(session.params)
        low["layers"] = [reference.lower_precision(layer, "fp8")
                         for layer in session.params["layers"]]
        low["lm_head"] = reference._LOW["fp8"](session.params["lm_head"])
        session.engine.params = low
        record = session.measure(3.0, cell["params"]["rate_rps"])
        assert not record["compiles_in_window"]
        engine = session.engine
        assert engine.moe_passes > 0 and engine.diffusion_blocks > 0
        assert engine.moe_assignments["zero"] == 0
        assert engine.moe_assignments["absent"] == 0
        assert engine.diffusion_tokens_committed == engine.tokens_generated
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
