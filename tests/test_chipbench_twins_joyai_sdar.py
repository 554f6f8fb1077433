"""Tier-1 runs a whole window of two more configurations' small twins
(``tests/test_chipbench_twins.py`` has the first two, and says why they are
several files): ``chipbench/tests/test_joyai_twin.py`` (the second latent,
routed block's) and ``test_sdar_twin.py`` (the diffusion block's), each
served through the normal path, judged against its plain reference, and
failed by its lower-precision control — the diffusion block's also by the
program that commits in index order."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.tests import test_joyai_twin as _joyai  # noqa: E402
from chipbench.tests import test_sdar_twin as _sdar  # noqa: E402

pytestmark = pytest.mark.usefixtures("chipbench_apart")

# each twin's cases under names of their own (the files give theirs the
# same three)
test_the_joyai_cell_names_the_same_modules_as_its_twin = \
    _joyai.test_the_cell_names_the_same_modules_as_its_twin
test_a_whole_window_of_the_joyai_twin_is_correct = \
    _joyai.test_a_whole_window_of_the_twin_is_correct
test_the_joyai_twins_lower_precision_is_not_correct = \
    _joyai.test_the_twins_lower_precision_is_not_correct

# ... and the diffusion block's
test_the_sdar_cell_names_the_same_modules_as_its_twin = \
    _sdar.test_the_cell_names_the_same_modules_as_its_twin
test_a_whole_window_of_the_sdar_twin_is_correct = \
    _sdar.test_a_whole_window_of_the_twin_is_correct
test_the_sdar_twins_lower_precision_is_not_correct = \
    _sdar.test_the_twins_lower_precision_is_not_correct
test_the_sdar_twin_committing_in_index_order_is_not_correct = \
    _sdar.test_the_twin_committing_in_index_order_is_not_correct
