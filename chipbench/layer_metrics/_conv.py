"""What the readers of ``kubeshare.engine.conv`` share: the span an engine
whose model's layers name the short convolution makes once a dispatch, inside
``kubeshare.engine.consume``, with what the dispatch carried as attributes —
``lanes`` (the decode lanes and the chunk's), ``passes`` (the span's steps, 0
where no lane decoded), ``state_reads`` (lane-passes x convolution layers
that read a slot's state), ``resets`` (chunks that began at row 0: they read
zeros instead) and ``chunk`` (the rows of the prefill chunk it carried, 0 for
none).  Read over the traced tail of the window.

A program without the span (every engine of another model; the parent of the
PR that brought it) gives every reader here nothing to read: each returns
None and never raises.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from chipbench.layer_metrics import _readers, _spans, _stages

COUNTS = ("lanes", "passes", "state_reads", "resets", "chunk")


def spans_of(run: Dict) -> Optional[List]:
    """The conv spans inside the traced tail; None where there is none or
    one lacks an attribute."""
    spans = _spans.of(run)
    if spans is None:
        return None
    found = spans.inside("engine.conv")
    if not found or any(name not in s.attrs for s in found
                        for name in COUNTS):
        return None
    return found


def conv_roofline(run: Dict) -> Optional[float]:
    """The least time the spans imply for the short convolutions (the
    configuration's ``conv_min_seconds``: a pass's larger of its bytes at
    the HBM rate and its operations at the bf16 peak) over stage ``conv``'s
    seconds, in percent — the mechanism's share of its roofline whatever
    implements it.  The spans are made when the next step consumes a
    dispatch: their seconds are scaled to the launches booked."""
    booked = _stages.of(run)
    found = spans_of(run) if booked is not None else None
    roof = run["roofline"]
    if found is None or not hasattr(roof, "conv_min_seconds"):
        return None
    seconds = sum(l.stages.get("conv", 0.0) for l in booked.launches)
    if seconds <= 0:
        return None
    peaks = _readers.roofline.peaks(run["device_kind"])
    least = sum(roof.conv_min_seconds(
        run["tc"], peaks, int(s.attrs["lanes"]), int(s.attrs["passes"]),
        int(s.attrs["chunk"])) for s in found)
    least *= len(booked.launches) / len(found)
    return least / seconds * 100.0
