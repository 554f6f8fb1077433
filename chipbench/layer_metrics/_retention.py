"""What the readers of ``kubeshare.engine.retention`` share: the span an
engine of a ``retention`` block makes once a dispatch that carried such
lanes, inside ``kubeshare.engine.consume``, with what the dispatch carried as
attributes — ``lanes`` (the decode lanes and the chunk's), ``state_lanes``
(the decode lanes that have folded a key block: each reads its state once a
step), ``passes`` (the span's steps), ``state_reads`` (``state_lanes x
passes``, and one more where the chunk's lane read a state), ``tail_rows``
(unfolded rows read as keys and values, over lanes and passes), ``folds``,
``folded_rows``, ``pages_freed`` (behind the folds, less the pages the same
lanes drew for the rows ahead) and ``chunk`` (the rows of the prefill chunk it
carried, 0 for none).  Read over the traced tail of the window.

A program without the span (every engine of another block; the parent of the
PR that brought it) gives every reader here nothing to read: each returns
None and never raises.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from chipbench.layer_metrics import _readers, _spans, _stages

COUNTS = ("lanes", "state_lanes", "passes", "state_reads", "tail_rows",
          "folds", "folded_rows", "pages_freed", "chunk")


def spans_of(run: Dict) -> Optional[List]:
    """The retention spans inside the traced tail; None where there is none
    or one lacks an attribute."""
    spans = _spans.of(run)
    if spans is None:
        return None
    found = spans.inside("engine.retention")
    if not found or any(name not in s.attrs for s in found
                        for name in COUNTS):
        return None
    return found


def totals(run: Dict) -> Optional[Dict[str, int]]:
    found = spans_of(run)
    if found is None:
        return None
    counts = {name: sum(int(s.attrs[name]) for s in found)
              for name in COUNTS}
    counts["spans"] = len(found)
    return counts


def _least_bytes(run: Dict, counts: Dict[str, int]) -> Optional[float]:
    """The least the mechanism had to move for what the spans carried, by
    the configuration's ``retention_min_bytes``: a state a lane-pass that
    read one, the unfolded rows with their gates, and a state read and
    written and a key block of rows a fold."""
    roof = run["roofline"]
    if not hasattr(roof, "retention_min_bytes"):
        return None
    return roof.retention_min_bytes(
        run["tc"], counts["state_reads"], counts["tail_rows"],
        counts["folds"])


def retention_hbm_roofline(run: Dict) -> Optional[float]:
    """The least time HBM could take for the mechanism over stage
    ``retention``'s seconds, in percent — its share of its roofline
    whatever implements it.  The spans are made when the next step consumes
    a dispatch: their bytes are scaled to the launches booked.  A decode
    step's state read is 2 x 5 rows of products a value, far under the 240
    FLOP a byte at which the v5e turns, so bytes bound it; the chunk's
    ``phi(q)^T S`` and a fold are compute-bound and in the same stage, so
    the share reads low where they are much of it."""
    booked = _stages.of(run)
    counts = totals(run) if booked is not None else None
    if counts is None:
        return None
    least = _least_bytes(run, counts)
    seconds = sum(l.stages.get("retention", 0.0) for l in booked.launches)
    if least is None or seconds <= 0:
        return None
    least *= len(booked.launches) / counts["spans"]
    peak = _readers.roofline.peaks(run["device_kind"])["hbm_bytes_per_s"]
    return least / peak / seconds * 100.0


def state_bytes_share(run: Dict) -> Optional[float]:
    """Those bytes over all that the same dispatches had to read, in
    percent: how much of a step IS the mechanism.  All: the mechanism's,
    and the weights once a pass of a span (the chunk could ride the
    first)."""
    counts = totals(run)
    if counts is None or not counts["passes"]:
        return None
    least = _least_bytes(run, counts)
    if least is None:
        return None
    weights = run["roofline"].decode_step_weight_bytes(run["tc"]) \
        * counts["passes"]
    return least / (least + weights) * 100.0


def tail_rows_per_lane(run: Dict) -> Optional[float]:
    """Unfolded rows a lane read a pass: what the paged tails hold, beside
    states that do not grow.  Under a key block and a half where lanes fold
    as they should; the request's whole length where they do not."""
    counts = totals(run)
    found = spans_of(run)
    if counts is None:
        return None
    lane_passes = sum(
        (int(s.attrs["lanes"]) - (int(s.attrs["chunk"]) > 0))
        * int(s.attrs["passes"]) + (int(s.attrs["chunk"]) > 0)
        for s in found)
    return counts["tail_rows"] / lane_passes if lane_passes else None
