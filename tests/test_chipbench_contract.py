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
