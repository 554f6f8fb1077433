"""What the readers of ``kubeshare.engine.routing`` share: the span a routed
block's engine makes once a dispatch, inside ``kubeshare.engine.consume``,
with the dispatch's routing counts as attributes — ``rows`` and ``passes``
(the rows and the expert-layer passes the dispatch carried: one pass a
prefill chunk, one a decode step of a span), ``held`` / ``zero`` / ``absent``
(router choices by where the chosen expert lives; they add up to ``top_k x
layers x rows``) and ``touched`` (held experts that got at least one row,
summed over passes and layers).  Read over the traced tail of the window.

A program without the span (the dense block's engine; the parent of the PR
that brought it) gives every reader here nothing to read: each returns None
and never raises.
"""

from __future__ import annotations

from typing import Dict, Optional

from chipbench.layer_metrics import _readers, _spans

COUNTS = ("rows", "passes", "held", "zero", "absent", "touched")


def totals(run: Dict) -> Optional[Dict[str, int]]:
    """The counts summed over the routing spans inside the traced tail."""
    spans = _spans.of(run)
    if spans is None:
        return None
    routed = spans.inside("engine.routing")
    if not routed:
        return None
    out = {name: sum(int(s.attrs.get(name, 0)) for s in routed)
           for name in COUNTS}
    out["spans"] = len(routed)
    return out


def held_expert_slots(run: Dict, counts: Dict[str, int]) -> int:
    """Expert-layer passes x layers x experts held: how many (pass, layer,
    expert) triples could have got rows."""
    tc = run["tc"]
    held = tc.get("experts_held") or tc["n_routed_experts"]
    return counts["passes"] * tc["n_layers"] * held


def zero_share(run: Dict) -> Optional[float]:
    counts = totals(run)
    if counts is None:
        return None
    chosen = counts["held"] + counts["zero"] + counts["absent"]
    return counts["zero"] / chosen * 100.0 if chosen else None


def held_rows_per_expert(run: Dict) -> Optional[float]:
    counts = totals(run)
    if counts is None:
        return None
    slots = held_expert_slots(run, counts)
    return counts["held"] / slots if slots else None


def held_touched_share(run: Dict) -> Optional[float]:
    counts = totals(run)
    if counts is None:
        return None
    slots = held_expert_slots(run, counts)
    return counts["touched"] / slots * 100.0 if slots else None


def mixed_routed_hbm_roofline(run: Dict) -> Optional[float]:
    """``_readers.mixed_hbm_roofline`` with the experts the routing touched
    added to the least bytes: every touched (pass, layer, expert) reads that
    expert's three matrices once.  Over the mixed dispatches of the traced
    tail; the touched experts are those of the routing spans of the same
    tail (every dispatch of a backlog is mixed)."""
    counts = totals(run)
    roof = run["roofline"]
    if counts is None or not hasattr(roof, "expert_bytes"):
        return None
    steps = _readers._steps_in_trace(run, "mixed")
    if not steps:
        return None
    span = run["record"]["decode_span"]
    peak = _readers.roofline.peaks(run["device_kind"])["hbm_bytes_per_s"]
    least = sum(span * roof.decode_step_min_bytes(run["tc"], sum(s["rows"]))
                for s in steps)
    # the spans and the steps cover the same tail but for its two ends:
    # scale the touched experts to the steps that were counted
    touched = counts["touched"] * len(steps) / counts["spans"]
    least += touched * roof.expert_bytes(run["tc"])
    busy = sum(run["trace"].step_busy_s[s["i"]] for s in steps)
    return least / peak / busy * 100.0 if busy > 0 else None
