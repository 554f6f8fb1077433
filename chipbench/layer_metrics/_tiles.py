"""What the readers of the routing spans' NEWER attributes share.
``kubeshare.engine.routing`` (see ``_routing.py``) also carries ``live`` (the
rows that chose: an idle lane and a chunk's padding choose nothing), ``tiles``
(the tiles the expert loop ran: each one held expert over at most a tile's
rows, reading that expert's matrices once) and ``tile_rows`` (their rows, an
expert's last tile's padding included).  ``_routing.totals`` sums the older
counts; ``sums`` adds these, over the same spans of the traced tail.

A program whose spans lack an attribute (the parent of the PR that brought
it), or that has no such span (the dense block's engine), gives every reader
here nothing to read: each returns None and never raises.
"""

from __future__ import annotations

from typing import Dict, Optional

from chipbench.layer_metrics import _readers, _routing, _spans

NEWER = ("live", "tiles", "tile_rows")


def sums(run: Dict) -> Optional[Dict[str, int]]:
    """``_routing.totals`` with the newer counts beside the older ones."""
    counts = _routing.totals(run)
    if counts is None:
        return None
    routed = _spans.of(run).inside("engine.routing")
    if any(name not in s.attrs for s in routed for name in NEWER):
        return None
    for name in NEWER:
        counts[name] = sum(int(s.attrs[name]) for s in routed)
    return counts


def rows_per_touched_expert(run: Dict) -> Optional[float]:
    counts = _routing.totals(run)
    if counts is None or not counts["touched"]:
        return None
    return counts["held"] / counts["touched"]


def tile_fill_share(run: Dict) -> Optional[float]:
    counts = sums(run)
    if counts is None or not counts["tile_rows"]:
        return None
    return counts["held"] / counts["tile_rows"] * 100.0


def mixed_expert_bytes_share(run: Dict) -> Optional[float]:
    """Of the least bytes ``_routing.mixed_routed_hbm_roofline`` divides by
    (the weights outside the experts once a decode step, the live rows, and
    every touched expert's three matrices once), the touched experts'
    part: the same steps, the same spans, the same scaling between them."""
    counts = _routing.totals(run)
    if counts is None:
        return None
    roof = run["roofline"]
    if not hasattr(roof, "expert_bytes"):
        return None
    steps = _readers._steps_in_trace(run, "mixed")
    if not steps:
        return None
    span = run["record"]["decode_span"]
    outside = sum(span * roof.decode_step_min_bytes(run["tc"], sum(s["rows"]))
                  for s in steps)
    experts = counts["touched"] * len(steps) / counts["spans"] \
        * roof.expert_bytes(run["tc"])
    return experts / (outside + experts) * 100.0
