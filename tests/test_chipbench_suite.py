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
reader expects of the program.  The whole-window cases of the twins' files
are in ``tests/test_chipbench_twins.py`` and the two files beside it, so
that ``--dist loadfile`` can give each to a worker of its own."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.tests.test_chipbench import *  # noqa: E402,F401,F403
from chipbench.tests.test_spans import *  # noqa: E402,F401,F403
from chipbench.tests.test_tiles import *  # noqa: E402,F401,F403
from chipbench.tests import test_conv_readers as _conv  # noqa: E402
from chipbench.tests import test_diffusion_readers as _diffusion  # noqa: E402
from chipbench.tests import test_kinds_readers as _kinds  # noqa: E402
from chipbench.tests import test_retention_readers as _retention  # noqa: E402
from chipbench.tests import test_stages as _stages  # noqa: E402

pytestmark = pytest.mark.usefixtures("chipbench_apart")

# ``test_stages.py`` takes every metric named ``step.stage_*`` for one of PR
# 38's 14 (``NAMES``) and counts them; PR 41 appended a fifteenth,
# ``step.stage_ms.retention.backlog``, whose stage no table of those cases
# has, and PR 43 a sixteenth, ``step.stage_ms.conv.backlog``.  Its cases run
# here over the 14 they were written for; the listing below counts all 16.
RETENTION_STAGE = "step.stage_ms.retention.backlog"
CONV_STAGE = "step.stage_ms.conv.backlog"
ALL_STAGE_NAMES = list(_stages.NAMES)
_stages.NAMES = [n for n in ALL_STAGE_NAMES
                 if n not in (RETENTION_STAGE, CONV_STAGE)]

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

# ... and the retention readers' (the same three names again)
test_retention_readers_over_spans_with_the_attributes = \
    _retention.test_readers_over_spans_with_the_attributes
test_retention_spans_that_lack_what_a_reader_reads_give_nothing = \
    _retention.test_spans_that_lack_what_a_reader_reads_give_nothing
test_a_program_without_the_retention_span_gives_nothing = \
    _retention.test_a_program_without_the_span_gives_nothing

# ... and the conv readers' (the same three names once more)
test_conv_readers_over_spans_with_the_attributes = \
    _conv.test_readers_over_spans_with_the_attributes
test_conv_spans_that_lack_what_a_reader_reads_give_nothing = \
    _conv.test_spans_that_lack_what_a_reader_reads_give_nothing
test_a_program_without_the_conv_span_gives_nothing = \
    _conv.test_a_program_without_the_span_gives_nothing

# ... and the readers' of a cache by layer kind (the same three)
test_kinds_readers_over_spans_with_the_attributes = \
    _kinds.test_readers_over_spans_with_the_attributes
test_kinds_spans_that_lack_what_a_reader_reads_give_nothing = \
    _kinds.test_spans_that_lack_what_a_reader_reads_give_nothing
test_a_program_without_the_kinds_span_gives_nothing = \
    _kinds.test_a_program_without_the_span_gives_nothing

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


def test_every_stage_metric_has_its_file_and_its_cells():
    """``test_stages.py``'s case held PR 38's 14 metrics to be the LAST 14 of
    ``per_layer`` over exactly the cells of that day; a configuration of
    another block has since appended its cell to the stages it has and four
    metrics after them (PR 41: a `benchmark` PR's to repair there, PERF.md
    section 7, item 11), another its cell and two more (PR 43, with a
    second dense backlog cell), and a third its cell — to the stages it has
    and not to the kernel's share, which counts a held row as read in every
    layer — and four metrics that are no stage's (PR 47).  The same
    statements, over the cells of today: PR
    38's 14 in their order before anything later, a file each that says
    what its entry says, and each over the cells whose programs have the
    stage."""
    names = _stages.NAMES
    assert len(names) == 14 and len(ALL_STAGE_NAMES) == 16
    per_layer = {m["name"]: m for m in _stages.BENCH["per_layer"]}
    e2e = {m["name"]: m for m in _stages.BENCH["end_to_end"]}
    listed = [m["name"] for m in _stages.BENCH["per_layer"]]
    first = listed.index(names[0])
    assert listed[first:first + 14] == names  # appended then, in order
    assert listed[first + 14:] == [
        RETENTION_STAGE, "step.retention_hbm_roofline.backlog",
        "retention.state_bytes_share.backlog",
        "retention.tail_rows_per_lane.backlog",  # appended since (PR 41)
        CONV_STAGE, "step.conv_roofline.backlog",  # ... and since (PR 43)
        "step.mixed_kinds_routed_hbm_roofline.backlog",
        "step.attend_kinds_kernel_hbm_roofline.backlog",
        "kv.window_read_share.backlog",
        "kv.pool_bytes_per_context_row.backlog"]  # ... and since (PR 47)
    conv = {"lfm2-pp5.gen.topics"}  # routed too, behind convolutions
    kinds = {"smallthinker-pp7.gen.longmix"}  # routed, a cache by kind
    routed = {"lcf-ep32.gen.topics", "joyai-pp8.gen.topics",
              "sdar-pp8.gen.topics"} | conv
    dense = {"sc2-3b.gen.backlog", "scb-1b.gen.backlog"}
    retention = {"brumby-pp8.gen.topics"}  # dense FFN, no kernel, no expert
    for name in ALL_STAGE_NAMES:
        metric, module = per_layer[name], _stages._reader(name)
        assert (module.LAYER, module.UNIT, module.MOVES) == \
            (metric["layer"], metric["unit"], metric["moves"])
        assert metric["source"] == "device_trace"
        cells = set(metric["workloads"])
        assert cells <= set(e2e[metric["moves"]]["workloads"])
        assert "scb-1b.gen.shared" not in cells
        if name.endswith(".rate"):
            assert cells == {"scb-1b.gen.rate"}
        elif ".retention." in name:
            assert cells == retention
        elif ".conv." in name:
            assert cells == conv
        elif "experts" in name:
            assert cells == routed | kinds
        elif "attend_kernel" in name:  # every layer reads every held row
            assert cells == routed | dense
        elif ".ffn." in name:  # `sdar`'s every feed-forward is the experts'
            assert cells == routed - {"sdar-pp8.gen.topics"} | dense \
                | retention
        else:
            assert cells == routed | dense | retention | kinds
