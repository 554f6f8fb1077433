"""Record the small trace kept under ``chipbench/tests/data``: the tiny
rehearsal configuration, on the chip, with the profiler on.

    chiprun -- python3 -m chipbench.tools.record_fixture
"""

from __future__ import annotations

import json
import os
import shutil

from chipbench import run
from chipbench.tests.rehearse import tiny_cell
from chipbench.trace import find_xplane

if __name__ == "__main__":
    result = run.run_cell(tiny_cell("rate"), seed=5, seconds=0.6, trace=True)
    out = os.path.join(run.REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    source = find_xplane(os.path.join(run.STATE_DIR, "trace"))
    shutil.copy(source, os.path.join(out, "tiny.xplane.pb"))
    print(json.dumps({"fixture_bytes": os.path.getsize(source),
                      "busy_s": result["device"]["busy_s"],
                      "window_s": result["device"]["window_s"],
                      "breakdown": result["breakdown"]}))
