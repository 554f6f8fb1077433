"""Look at a trace by hand: planes, lines, and the first events of each.

    python3 -m chipbench.tools.trace_dump <log_dir or .xplane.pb> [events]
"""

from __future__ import annotations

import os
import sys

from chipbench.trace import _load, find_xplane


def dump(path: str, events: int = 4) -> None:
    if os.path.isdir(path):
        path = find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in _load(path).planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), len(lines), "lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            print("  LINE", repr(line.name), len(evs), "events")
            for e in evs[:events]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns,
                      dict(list(e.stats)[:6]))


if __name__ == "__main__":
    dump(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 4)
