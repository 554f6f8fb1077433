"""What the readers of ``kubeshare.engine.diffusion`` share: the span an
engine that generates by diffusion over blocks makes once a dispatch that
carried lanes, inside ``kubeshare.engine.consume``, with what the dispatch
carried as attributes — ``lanes``, ``passes`` (denoising lane-passes) and
``commit_passes``, ``rows`` (the query rows the lanes computed: a block's a
lane) and ``masked_rows`` (of them, those still masked going in),
``committed`` (the tokens the dispatch served), ``blocks_done``, ``kv_rows``
(the lanes' cached rows the passes attended: the harness's own ``rows`` counts
a request only after its first token, and a lane here runs passes before it
has served one), and of the same dispatch ``chunk`` (the rows of the prefill
chunk it carried beside the lanes, 0 for none) and ``touched`` (the (layer,
expert) pairs its routing gave a row).  Read over the traced tail of the
window.

A program without the span (every engine that generates one token after
another; the parent of the PR that brought it) gives every reader here nothing
to read: each returns None and never raises.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from chipbench.layer_metrics import _readers, _spans

COUNTS = ("lanes", "passes", "commit_passes", "rows", "masked_rows",
          "committed", "blocks_done", "kv_rows")


def spans_of(run: Dict) -> Optional[List]:
    """The diffusion spans inside the traced tail; None where there is none
    or one lacks an attribute."""
    spans = _spans.of(run)
    if spans is None:
        return None
    found = spans.inside("engine.diffusion")
    if not found or any(name not in s.attrs for s in found
                        for name in COUNTS + ("chunk", "touched")):
        return None
    return found


def totals(run: Dict) -> Optional[Dict[str, int]]:
    found = spans_of(run)
    if found is None:
        return None
    return {name: sum(int(s.attrs[name]) for s in found) for name in COUNTS}


def rows_per_token(run: Dict) -> Optional[float]:
    counts = totals(run)
    if counts is None or not counts["committed"]:
        return None
    return counts["rows"] / counts["committed"]


def passes_per_block(run: Dict) -> Optional[float]:
    counts = totals(run)
    if counts is None or not counts["blocks_done"]:
        return None
    return (counts["passes"] + counts["commit_passes"]) \
        / counts["blocks_done"]


def _dispatches(run: Dict, chunk: bool):
    """(the dispatches of the traced tail that carried diffusion lanes and
    no chunk — the harness's kind ``decode`` — or, with ``chunk``, lanes
    beside a chunk — its kind ``mixed`` — and the spans of such
    dispatches); None where either is missing."""
    found = spans_of(run)
    if found is None:
        return None
    steps = _readers._steps_in_trace(run, "mixed" if chunk else "decode")
    spans = [s for s in found if (int(s.attrs["chunk"]) > 0) == chunk]
    if not steps or not spans:
        return None
    return steps, spans


def _alone(run: Dict):
    return _dispatches(run, chunk=False)


def diffusion_device_ms(run: Dict) -> Optional[float]:
    """Device-busy milliseconds of one dispatch that carries diffusion lanes
    alone: one pass over every lane's block."""
    both = _alone(run)
    if both is None:
        return None
    steps, _ = both
    busy = sum(run["trace"].step_busy_s[s["i"]] for s in steps)
    return busy / len(steps) * 1e3


def diffusion_routed_hbm_roofline(run: Dict) -> Optional[float]:
    """The least time HBM could take for those dispatches over the device
    time they took, in percent.  Least bytes, by the configuration's
    ``pass_min_bytes``: the weights outside the experts once a pass, the
    three matrices of every (layer, expert) the pass's routing touched, and
    the lanes' cached K/V rows — ``touched`` and ``kv_rows`` of the spans of
    the same dispatches.  A pass of 128 rows is far under the 240 FLOP a
    byte at which the v5e turns, so bytes bound."""
    both = _alone(run)
    roof = run["roofline"]
    if both is None or not hasattr(roof, "pass_min_bytes"):
        return None
    steps, alone = both
    peak = _readers.roofline.peaks(run["device_kind"])["hbm_bytes_per_s"]
    least = sum(roof.pass_min_bytes(run["tc"], int(s.attrs["kv_rows"]),
                                    int(s.attrs["touched"])) for s in alone)
    # the spans and the steps cover the same tail but for its two ends (a
    # dispatch's span is made when the next step consumes it): scale the
    # spans' bytes to the steps that were timed
    least *= len(steps) / len(alone)
    busy = sum(run["trace"].step_busy_s[s["i"]] for s in steps)
    return least / peak / busy * 100.0 if busy > 0 else None


def mixed_diffusion_routed_hbm_roofline(run: Dict) -> Optional[float]:
    """The same share of the dispatches that carried a prefill chunk beside
    the lanes (the harness's kind ``mixed``): one program, the pass over the
    lanes' blocks and the chunk's.  Least bytes: the weights outside the
    experts ONCE (the chunk could ride the lanes' pass), the lanes' cached
    rows (the prefilling lane's own context is left out, as
    ``_readers.mixed_hbm_roofline`` leaves it), and every (layer, expert)
    the dispatch touched once: ``touched`` counts a pair in the lanes' pass
    and again in the chunk's, so half of it, which is the least the two
    passes' union can be.  640 rows are far under the 240 FLOP a byte at
    which the v5e turns, so bytes bound."""
    both = _dispatches(run, chunk=True)
    roof = run["roofline"]
    if both is None or not hasattr(roof, "pass_min_bytes"):
        return None
    steps, mixed = both
    peak = _readers.roofline.peaks(run["device_kind"])["hbm_bytes_per_s"]
    least = sum(roof.pass_min_bytes(run["tc"], int(s.attrs["kv_rows"]),
                                    int(s.attrs["touched"]) / 2)
                for s in mixed)
    least *= len(steps) / len(mixed)  # the tail's two ends, as above
    busy = sum(run["trace"].step_busy_s[s["i"]] for s in steps)
    return least / peak / busy * 100.0 if busy > 0 else None
