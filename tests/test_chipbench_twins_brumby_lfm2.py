"""Tier-1 runs a whole window of the last two configurations' small twins
(``tests/test_chipbench_twins.py`` has the first two, and says why they are
several files): ``chipbench/tests/test_brumby_twin.py`` (the retention
block's) and ``test_lfm2_twin.py`` (the convolution-attention hybrid's),
each served through the normal path, judged against its plain reference,
and failed by its lower-precision control — the retention block's also by
the program that serves from its unfolded rows alone, the state forgotten,
the hybrid's also by the program whose convolutions' states are zeroed at
every dispatch."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.tests import test_brumby_twin as _brumby  # noqa: E402
from chipbench.tests import test_lfm2_twin as _lfm2  # noqa: E402

pytestmark = pytest.mark.usefixtures("chipbench_apart")

# each twin's cases under names of their own (the files give theirs the
# same three)
test_the_brumby_cell_names_the_same_modules_as_its_twin = \
    _brumby.test_the_cell_names_the_same_modules_as_its_twin
test_a_whole_window_of_the_brumby_twin_is_correct = \
    _brumby.test_a_whole_window_of_the_twin_is_correct
test_the_brumby_twins_lower_precision_is_not_correct = \
    _brumby.test_the_twins_lower_precision_is_not_correct
test_the_brumby_twin_serving_from_the_tail_alone_is_not_correct = \
    _brumby.test_the_twin_serving_from_the_tail_alone_is_not_correct

# ... and the hybrid's
test_the_lfm2_cell_names_the_same_modules_as_its_twin = \
    _lfm2.test_the_cell_names_the_same_modules_as_its_twin
test_a_whole_window_of_the_lfm2_twin_is_correct = \
    _lfm2.test_a_whole_window_of_the_twin_is_correct
test_the_lfm2_twins_lower_precision_is_not_correct = \
    _lfm2.test_the_twins_lower_precision_is_not_correct
test_the_lfm2_twin_with_its_state_zeroed_is_not_correct = \
    _lfm2.test_the_twin_with_its_state_zeroed_is_not_correct
