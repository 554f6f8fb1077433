"""Tier-1 runs a whole window of the seventh configuration's small twin
(``tests/test_chipbench_twins.py`` says why the twins are several files):
``chipbench/tests/test_smallthinker_twin.py`` — full attention without
rotation in one layer of four, a window with rotation in the other three,
served from a cache by layer kind through the normal path, judged against its
plain reference, and failed by its lower-precision control, by the program
whose window layers attend every row and by the program that rotates in its
full layers too."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.tests.test_smallthinker_twin import (  # noqa: E402,F401
    test_a_whole_window_of_the_twin_is_correct,
    test_the_cell_names_the_same_modules_as_its_twin,
    test_the_twin_that_rotates_in_its_full_layers_is_not_correct,
    test_the_twin_whose_window_layers_attend_every_row_is_not_correct,
    test_the_twins_lower_precision_is_not_correct)

pytestmark = pytest.mark.usefixtures("chipbench_apart")
