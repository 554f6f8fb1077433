"""Tier-1 holds every configuration of the benchmark to the harness's
contract: ``chipbench/tests/test_contract.py``'s cases (a configuration's
three modules expose what ``chipbench/README.md`` says, its count of a block
is what the program allocates, a count that lies fails set-up), collected
here so that a module a later PR adds is held to it on every PR after.
Light: no native runtime, nothing the size of a cell is allocated."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.tests import test_contract as contract  # noqa: E402
from chipbench.tests.test_contract import *  # noqa: E402,F401,F403

# "The defaults are the very modules that ran before" is a statement about
# the cells whose configuration names no module of its own (the dense
# block's); there it is parametrised over every cell of BENCHMARK.json,
# which held until a configuration of another block got a cell.
_DENSE_CELLS = [
    w["name"] for w in contract.BENCH["workloads"]
    if contract.run.config_modules(contract.CONFIGS[w["config"]])
    == contract.run.MODULES]


@pytest.mark.parametrize("cell", _DENSE_CELLS + ["tiny"])
def test_the_defaults_are_the_very_modules_that_ran_before(cell):
    contract.test_the_defaults_are_the_very_modules_that_ran_before(cell)


# "The count of a block is what the program allocates" sums the shapes of the
# pool's K and V alone, which were all of a pool until a block got a third
# array (a 'retention' block's float32 log gate a row, ``PagedKVPool.gate``).
# The harness's own check (``system.pool_bytes`` in ``build_engine``) counts
# every array of the pool; so does the case here, which shadows the harness's
# over the same configurations (a `benchmark` PR's to repair there: PERF.md
# section 7, item 11).
@pytest.mark.parametrize("name", sorted(contract.CONFIGS))
def test_the_count_of_a_block_is_what_the_program_allocates(name):
    import jax

    from kubeshare_tpu.serving.kv_blocks import init_paged_pool

    config_file = contract.CONFIGS[name]
    counts = contract.run.cell_module(
        {"modules": contract.run.config_modules(config_file)}, "roofline")
    e = config_file["engine"]
    per_block = counts.kv_bytes_per_row(config_file["transformer_config"]) \
        * e["block_size"]
    num_blocks = e["pool_bytes"] // per_block + 1
    make = lambda: init_paged_pool(contract._engine_config(config_file),
                                   num_blocks, e["block_size"])
    if name.startswith("tiny"):
        assert contract.system.pool_bytes(make()) == per_block * num_blocks
    shapes = jax.eval_shape(lambda: make().arrays())
    # K and V; a log gate beside them; a K and a V a KIND of layer
    assert len(shapes) == (3 if "brumby" in name else
                           4 if "smallthinker" in name else 2)
    assert sum(x.size * x.dtype.itemsize for x in shapes) \
        == per_block * num_blocks
