"""What a configuration's file may name, and what the harness then does with
it: light, no native runtime.  ``JAX_PLATFORMS=cpu python3 -m pytest
chipbench/tests/test_contract.py -q``."""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import roofline, run, system, weights  # noqa: E402
from chipbench.tests.rehearse import tiny_cell  # noqa: E402

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
EXPOSES = {"reference": ("served_gaps", "control_gaps", "summarize"),
           "weights": ("make_weights",),
           "roofline": ("kv_bytes_per_row", "decode_step_weight_bytes",
                        "decode_step_min_bytes")}


def _config_files():
    """Every configuration of BENCHMARK.json, and the small twins here."""
    for config in BENCH["configs"]:
        yield config["name"], run.load_json(REPO, config["file"])
    for name in sorted(os.listdir(os.path.join(HERE, "configs"))):
        yield name[:-len(".json")], run.load_json(HERE, "configs", name)


CONFIGS = dict(_config_files())


def _engine_config(config_file):
    import jax.numpy as jnp

    from kubeshare_tpu.models.transformer import TransformerConfig

    tc = dict(config_file["transformer_config"])
    tc["dtype"] = jnp.dtype(tc["dtype"])
    return TransformerConfig(**tc)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_a_configuration_names_modules_that_expose_the_contract(name):
    cell = {"modules": run.config_modules(CONFIGS[name])}
    assert set(cell["modules"]) == set(EXPOSES)
    for kind, functions in EXPOSES.items():
        module = run.cell_module(cell, kind)
        assert all(callable(getattr(module, f)) for f in functions), kind


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_count_of_a_block_is_what_the_program_allocates(name):
    """``kv_bytes_per_row x block_size x num_blocks`` against the pool
    ``init_paged_pool`` makes: by shape alone at the cells' sizes (1.5 GiB
    is not allocated here), in fact at the tiny ones."""
    import jax

    from kubeshare_tpu.serving.kv_blocks import init_paged_pool

    config_file = CONFIGS[name]
    counts = run.cell_module({"modules": run.config_modules(config_file)},
                             "roofline")
    e = config_file["engine"]
    per_block = counts.kv_bytes_per_row(config_file["transformer_config"]) \
        * e["block_size"]
    num_blocks = e["pool_bytes"] // per_block + 1
    make = lambda: init_paged_pool(_engine_config(config_file), num_blocks,
                                   e["block_size"])
    if name.startswith("tiny"):
        assert system.pool_bytes(make()) == per_block * num_blocks
    shapes = jax.eval_shape(lambda: (make().k, make().v))
    assert sum(x.size * x.dtype.itemsize for x in shapes) \
        == per_block * num_blocks


def test_todays_cells_are_sized_as_they_were():
    """The figures the inlined formula gave (PERF.md section 5)."""
    for name, per_block, blocks in (("starcoderbase-1b", 196_608, 8193),
                                    ("starcoder2-3b", 491_520, 2185)):
        config_file = CONFIGS[name]
        got = roofline.kv_bytes_per_row(config_file["transformer_config"]) \
            * config_file["engine"]["block_size"]
        assert got == per_block
        assert config_file["engine"]["pool_bytes"] // got + 1 == blocks


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]]
                         + ["tiny"])
def test_the_defaults_are_the_very_modules_that_ran_before(cell):
    loaded = (tiny_cell("rate") if cell == "tiny"
              else run.load_cell(cell, BENCH))
    assert run.cell_module(loaded, "weights").make_weights \
        is weights.make_weights
    assert run.cell_module(loaded, "roofline") is roofline
    assert loaded["modules"]["reference"] == "chipbench.reference"


@pytest.mark.parametrize("name", ["tiny", "tiny_moe"])
def test_a_count_that_lies_fails_set_up(name):
    """Half the true figure: 129 blocks are asked for where 65 fit, and
    the pool the program makes for them is twice what the count says."""
    config_file = CONFIGS[name]
    cell = {"modules": run.config_modules(config_file)}
    true = run.cell_module(cell, "roofline")
    liar = types.SimpleNamespace(
        __name__="liar",
        kv_bytes_per_row=lambda tc: true.kv_bytes_per_row(tc) // 2)
    params = run.cell_module(cell, "weights").make_weights(
        5, config_file["transformer_config"])
    engine = system.build_engine(config_file, params, None, true)
    assert engine.engine_config.num_blocks == 65
    with pytest.raises(RuntimeError,
                       match=r"liar.*264192 B for 129.*528384 B"):
        system.build_engine(config_file, params, None, liar)


def test_the_second_block_counts_its_routed_bytes():
    tc = CONFIGS["tiny_moe"]["transformer_config"]
    counts = run.cell_module(
        {"modules": run.config_modules(CONFIGS["tiny_moe"])}, "roofline")
    attention = 2 * 64 * 4 * 16 + 2 * 64 * 2 * 16 + 2 * 64
    dense_layer = attention + 2 * 64 * 128
    routed_layer = attention + 64 * 4 + 2 * 2 * 64 * 128  # router, 2 of 4
    assert counts.decode_step_weight_bytes(tc) == \
        2 * (dense_layer + routed_layer + 64 + 64 * 512)
    assert counts.kv_bytes_per_row(tc) == roofline.kv_bytes_per_row(tc) == 256
    assert counts.decode_step_min_bytes(tc, 10) == \
        counts.decode_step_weight_bytes(tc) + 2560
    # every expert read would be more; the dense count has no experts at all
    assert counts.decode_step_weight_bytes(tc) \
        > roofline.decode_step_weight_bytes(tc)
