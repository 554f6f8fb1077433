"""What the readers of a cache BY LAYER KIND share: an engine whose model
names the "window" attention kind keeps a pool, a table and an allocator a
kind, and says so in two places.  Its ``kubeshare.engine.launch`` spans carry
``window_rows`` beside ``rows``: over the dispatch's decode lanes, what a
window layer reads of a lane's rows (``min(rows held, window)``) beside what
a full layer reads (all of them).  And once a dispatch, inside
``kubeshare.engine.consume``, a ``kubeshare.engine.kv_kinds`` span:
``released`` and ``drawn`` (the window kind's pages handed back behind the
window and drawn for the rows ahead by this dispatch), ``live_full`` and
``live_window`` (the pages of each kind in use after it) and
``context_rows`` (the rows of the live lanes' contexts).  The bytes are the
configuration's (``kv_read_bytes_by_kind``, ``step_bytes_by_kind``,
``expert_bytes``).  Read over the traced tail of the window.

A program without the attribute or the span (every engine of a model that
caches under one table a lane; the parent of the PR that brought them), or a
count of bytes without the functions, gives every reader here nothing to
read: each returns None and never raises.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from chipbench.layer_metrics import _readers, _routing, _spans, _stages

KINDS_COUNTS = ("released", "drawn", "live_full", "live_window",
                "context_rows")
ROWS = ("rows", "window_rows")


def _decoding(launches: List) -> Optional[List]:
    """Of the launches, those whose lanes decoded; None where none did or
    one lacks a row count."""
    ran = [l for l in launches if int(l.attrs.get("lanes", 0)) > 0]
    if not ran or any(name not in l.attrs for l in ran for name in ROWS):
        return None
    return ran


def window_read_share(run: Dict) -> Optional[float]:
    """``window_rows`` over ``rows`` of the traced launches, in percent:
    what a window layer reads of what a full layer reads.  100: the window
    never binds."""
    spans = _spans.of(run)
    ran = _decoding(spans.launches()) if spans is not None else None
    if ran is None:
        return None
    rows = sum(int(l.attrs["rows"]) for l in ran)
    return (sum(int(l.attrs["window_rows"]) for l in ran) / rows * 100.0
            if rows else None)


def pool_bytes_per_context_row(run: Dict) -> Optional[float]:
    """The bytes of both kinds' pages in use over the rows of the live
    lanes' contexts, over the ``kv_kinds`` spans of the tail: what a row of
    context costs the pool.  Under one table a lane it is every layer's
    row, and more while a lane's reserved pages are still empty."""
    spans = _spans.of(run)
    found = spans.inside("engine.kv_kinds") if spans is not None else None
    if not found or any(name not in s.attrs for s in found
                        for name in KINDS_COUNTS):
        return None
    roof = run["roofline"]
    if not hasattr(roof, "kv_read_bytes_by_kind"):
        return None
    rows = sum(int(s.attrs["context_rows"]) for s in found)
    if not rows:
        return None
    page = run["cell"]["config_file"]["engine"]["block_size"]
    row = roof.kv_read_bytes_by_kind(run["tc"])
    held = sum(int(s.attrs["live_full"]) * row["full"]
               + int(s.attrs["live_window"]) * row["window"]
               for s in found) * page
    return held / rows


def attend_kinds_kernel_hbm_roofline(run: Dict) -> Optional[float]:
    """``_stages.attend_kernel_hbm_roofline`` counted by kind: the least
    time HBM could take for what the paged kernel's calls had to read — the
    lanes' rows in the full layers, their ``window_rows`` in the window
    layers, once a step of the span — over the seconds of the ``attention``
    operations named ``paged_*``, in percent, over the launches whose lanes
    ran the kernel.  The rows a span's steps add and a page's unread ends
    are left out."""
    booked = _stages.of(run)
    if booked is None:
        return None
    roof = run["roofline"]
    if not hasattr(roof, "kv_read_bytes_by_kind"):
        return None
    ran = [l for l in booked.launches
           if "kernel" in str(l.span.attrs.get("attend", "")).split("+")
           and int(l.span.attrs.get("lanes", 0)) > 0]
    if not ran or any(name not in l.span.attrs for l in ran
                      for name in ROWS):
        return None
    seconds = sum(l.kernel_s for l in ran)
    if seconds <= 0:
        return None
    row = roof.kv_read_bytes_by_kind(run["tc"])
    least = run["record"]["decode_span"] * sum(
        int(l.span.attrs["rows"]) * row["full"]
        + int(l.span.attrs["window_rows"]) * row["window"] for l in ran)
    peak = _readers.roofline.peaks(run["device_kind"])["hbm_bytes_per_s"]
    return least / peak / seconds * 100.0


def mixed_kinds_routed_hbm_roofline(run: Dict) -> Optional[float]:
    """The whole mixed dispatch's share of its HBM roofline, counted by
    kind: ``decode_span`` x (the weights outside the experts + ``rows`` in
    the full layers + ``window_rows`` in the window layers:
    ``step_bytes_by_kind``) + the experts the routing touched x
    ``expert_bytes``, at the chip's HBM rate, over the device-busy seconds
    of the mixed launches booked.  The touched experts are the routing
    spans' of the same tail, scaled to the launches counted; the chunk's
    own context and the rows a span's steps add are left out."""
    booked = _stages.of(run)
    counts = _routing.totals(run) if booked is not None else None
    if counts is None:
        return None
    roof = run["roofline"]
    if not hasattr(roof, "step_bytes_by_kind") \
            or not hasattr(roof, "expert_bytes"):
        return None
    ran = [l for l in booked.launches if l.span.attrs.get("kind") == "mixed"]
    if not ran or any(name not in l.span.attrs for l in ran
                      for name in ROWS):
        return None
    busy = sum(l.busy_s for l in ran)
    if busy <= 0:
        return None
    tc = run["tc"]
    least = run["record"]["decode_span"] * sum(
        roof.step_bytes_by_kind(tc, int(l.span.attrs["rows"]),
                                int(l.span.attrs["window_rows"]))
        for l in ran)
    least += counts["touched"] * len(ran) / counts["spans"] \
        * roof.expert_bytes(tc)
    peak = _readers.roofline.peaks(run["device_kind"])["hbm_bytes_per_s"]
    return least / peak / busy * 100.0
