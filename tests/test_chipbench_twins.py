"""Tier-1 runs a whole window of each configuration's small twin, as
``tests/test_chipbench_suite.py`` runs the harness's own cases:
``chipbench/tests/test_second_block.py`` (a configuration of another block
as new files only) and ``test_longcat_twin.py`` (the first latent, routed
block's twin under the modules its cell names), each served through the
normal path, judged against its plain reference, and failed by its
lower-precision control.  The other four twins are in
``tests/test_chipbench_twins_joyai_sdar.py`` and
``tests/test_chipbench_twins_brumby_lfm2.py``: a whole window is 20-60 s,
and a tier-1 worker holds a file for its whole length (``--dist
loadfile``)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.tests.test_longcat_twin import *  # noqa: E402,F401,F403
from chipbench.tests.test_second_block import *  # noqa: E402,F401,F403

pytestmark = pytest.mark.usefixtures("chipbench_apart")
