"""Tier-1 runs a whole window of each configuration's small twin, as
``tests/test_chipbench_suite.py`` runs the harness's own cases:
``chipbench/tests/test_second_block.py`` (a configuration of another block
as new files only), ``test_longcat_twin.py`` and ``test_joyai_twin.py`` (the
two latent, routed blocks' twins under the modules their cells name) and
``test_sdar_twin.py`` (the diffusion block's), each
served through the normal path, judged against its plain reference, and failed by its lower-precision
control (the diffusion block's also by the program that commits in index
order, the retention block's — ``test_brumby_twin.py`` — also by the program
that serves from its unfolded rows alone, the state forgotten, the
convolution-attention hybrid's — ``test_lfm2_twin.py`` — also by the program
whose convolutions' states are zeroed at every dispatch)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.tests import test_brumby_twin as _brumby  # noqa: E402
from chipbench.tests import test_joyai_twin as _joyai  # noqa: E402
from chipbench.tests import test_lfm2_twin as _lfm2  # noqa: E402
from chipbench.tests import test_sdar_twin as _sdar  # noqa: E402
from chipbench.tests.test_longcat_twin import *  # noqa: E402,F401,F403
from chipbench.tests.test_second_block import *  # noqa: E402,F401,F403

pytestmark = pytest.mark.usefixtures("chipbench_apart")

# the second twin's cases under names of their own (the two files give
# theirs the same three)
test_the_joyai_cell_names_the_same_modules_as_its_twin = \
    _joyai.test_the_cell_names_the_same_modules_as_its_twin
test_a_whole_window_of_the_joyai_twin_is_correct = \
    _joyai.test_a_whole_window_of_the_twin_is_correct
test_the_joyai_twins_lower_precision_is_not_correct = \
    _joyai.test_the_twins_lower_precision_is_not_correct

# ... and the third's
test_the_sdar_cell_names_the_same_modules_as_its_twin = \
    _sdar.test_the_cell_names_the_same_modules_as_its_twin
test_a_whole_window_of_the_sdar_twin_is_correct = \
    _sdar.test_a_whole_window_of_the_twin_is_correct
test_the_sdar_twins_lower_precision_is_not_correct = \
    _sdar.test_the_twins_lower_precision_is_not_correct
test_the_sdar_twin_committing_in_index_order_is_not_correct = \
    _sdar.test_the_twin_committing_in_index_order_is_not_correct

# ... and the fourth's
test_the_brumby_cell_names_the_same_modules_as_its_twin = \
    _brumby.test_the_cell_names_the_same_modules_as_its_twin
test_a_whole_window_of_the_brumby_twin_is_correct = \
    _brumby.test_a_whole_window_of_the_twin_is_correct
test_the_brumby_twins_lower_precision_is_not_correct = \
    _brumby.test_the_twins_lower_precision_is_not_correct
test_the_brumby_twin_serving_from_the_tail_alone_is_not_correct = \
    _brumby.test_the_twin_serving_from_the_tail_alone_is_not_correct

# ... and the fifth's
test_the_lfm2_cell_names_the_same_modules_as_its_twin = \
    _lfm2.test_the_cell_names_the_same_modules_as_its_twin
test_a_whole_window_of_the_lfm2_twin_is_correct = \
    _lfm2.test_a_whole_window_of_the_twin_is_correct
test_the_lfm2_twins_lower_precision_is_not_correct = \
    _lfm2.test_the_twins_lower_precision_is_not_correct
test_the_lfm2_twin_with_its_state_zeroed_is_not_correct = \
    _lfm2.test_the_twin_with_its_state_zeroed_is_not_correct
