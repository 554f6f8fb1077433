"""What the per-layer readers share.  Each metric is a file of its own named
after the metric; those of one quantity split by the end-to-end metric they
move (``.rate`` / ``.backlog``) call the same function here.

A reader gets ``run``: ``record`` (the window's counter and tokend ``STAT``
deltas, the benchmark's own step records), ``trace`` (the reduced profiler
trace, see ``chipbench/trace.py``), ``tc`` (the configuration's
``transformer_config``), ``roofline`` (the configuration's byte-count module:
the least bytes of a step are its count, ``chipbench.roofline`` where the
file names none), ``device_kind``, ``pod_a`` (pod A's name) and
``notes`` (what ``run.end_to_end`` works out besides the metrics).  It returns a number, or None
where it finds nothing to read.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from chipbench import roofline


def dispatches(record: Dict) -> int:
    """Device dispatches of the window: a fused (mixed) dispatch carries
    one prefill chunk and one decode span, so it is counted once."""
    c = record["counters"]
    return c["prefill_chunks"] + c["decode_steps"] - c["mixed_steps"]


def host_ms_per_dispatch(run: Dict) -> Optional[float]:
    """The engine's own host seconds (admit, plan, consume, tune, and the
    marshalling inside dispatch less the gated device wait) per dispatch."""
    record = run["record"]
    n = dispatches(record)
    if n <= 0:
        return None
    c = record["counters"]
    host = sum(c["host_seconds"].values())
    # host_seconds["dispatch"] holds the guard's wait and the gated device
    # run too; take out what the guard charged and what it waited
    gated = c["gated_ms"] / 1e3
    return max(0.0, host - gated - c["acquire_wait_s"]) / n * 1e3


def _steps_in_trace(run: Dict, kind: str) -> List[Dict]:
    trace = run["trace"]
    if trace is None:
        return []
    return [s for s in run["record"]["steps"]
            if s["kind"] == kind and s["i"] in trace.step_busy_s]


def mixed_device_ms(run: Dict) -> Optional[float]:
    """Device-busy milliseconds of one mixed dispatch (one prefill chunk of
    at most ``prefill_chunk`` tokens fused with a decode span), over the
    mixed dispatches inside the traced window."""
    steps = _steps_in_trace(run, "mixed")
    if not steps:
        return None
    busy = sum(run["trace"].step_busy_s[s["i"]] for s in steps)
    return busy / len(steps) * 1e3


def mixed_hbm_roofline(run: Dict) -> Optional[float]:
    """The least time HBM could take for those mixed dispatches over the
    device time they took, in percent.  Least bytes: the weights once per
    decode step of the span (the steps are sequential; the chunk could ride
    the first pass) and the decode lanes' live KV rows; the prefilling
    lane's own context (at most 3072 rows) is left out.  The chunk's
    FLOPs (256 tokens) take less time than one weight pass, so bytes
    bound."""
    steps = _steps_in_trace(run, "mixed")
    if not steps:
        return None
    span = run["record"]["decode_span"]
    peak = roofline.peaks(run["device_kind"])["hbm_bytes_per_s"]
    least = sum(span * run["roofline"].decode_step_min_bytes(
        run["tc"], sum(s["rows"]))
                for s in steps) / peak
    busy = sum(run["trace"].step_busy_s[s["i"]] for s in steps)
    return least / busy * 100.0 if busy > 0 else None


def decode_device_ms(run: Dict) -> Optional[float]:
    """Device-busy milliseconds of one decode step (one token for every
    lane), over the pure-decode dispatches inside the traced window."""
    steps = _steps_in_trace(run, "decode")
    if not steps:
        return None
    busy = sum(run["trace"].step_busy_s[s["i"]] for s in steps)
    return busy / (len(steps) * run["record"]["decode_span"]) * 1e3


def decode_hbm_roofline(run: Dict) -> Optional[float]:
    """The least time the chip's HBM could take for those decode steps —
    bf16 weights once a step, the live lanes' KV rows — over the device
    time they took, in percent.  Bound by bytes: a decode step of at most
    32 lanes is far under the 240 FLOP a byte at which the v5e turns."""
    steps = _steps_in_trace(run, "decode")
    if not steps:
        return None
    span = run["record"]["decode_span"]
    peak = roofline.peaks(run["device_kind"])["hbm_bytes_per_s"]
    least = sum(span * run["roofline"].decode_step_min_bytes(
        run["tc"], sum(s["rows"]))
                for s in steps) / peak
    busy = sum(run["trace"].step_busy_s[s["i"]] for s in steps)
    return least / busy * 100.0 if busy > 0 else None
