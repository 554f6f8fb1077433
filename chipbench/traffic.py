"""The one traffic generator.  A mix is a data file under ``traffic/``; this
module turns it, a rate and ``--seed`` into a stream of requests in the order
they are due.

Every seed gets the SAME schedule, with other token values (and, in
``run.py``, other weights): the stratified quantiles of the mix's length and
gap distributions, each once.  In an open-loop mix they come in an EVEN
order (``even_order``): any stretch of consecutive requests holds short and
long prompts, outputs and gaps in the proportions of the whole, so no five
seconds of a window offer much more work than any other, at any rate, and the
window cannot end inside a burst.  After the window the same schedule goes on
(block 1, 2, ...), unscored, so that the last scored requests are served at
the load the first ones were.  Three other schemes were measured on the chip
and dropped (PERF.md, Findings PR 23): a fresh random order per seed and one
cycle started at another point per seed both moved the tail of time to first
token by 15% and more from seed to seed, and one fixed random order put 17 of
80 requests into the window's last five seconds, with no arrivals after them.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ARRIVALS = ("exponential", "backlog")
TOKEN_LAWS = ("zipf",)
BACKLOG_ORDER_SEED = 1  # orders a backlog block's lengths, for every seed
# irrational steps of the even orders of prompts, outputs and gaps: far from
# each other, so that the three are paired without a pattern
_PROMPT_STEP = (math.sqrt(5) - 1) / 2
_OUTPUT_STEP = math.sqrt(2) - 1
_GAP_STEP = math.sqrt(3) - 1


@dataclass
class TrafficRequest:
    rid: str
    prompt: np.ndarray  # int32 tokens
    max_new: int
    due: float  # seconds after the window opens


def load_mix(name: str, directory: str = os.path.join(HERE, "traffic")) -> Dict:
    with open(os.path.join(directory, f"{name}.json")) as f:
        mix = json.load(f)
    if mix["arrivals"] not in ARRIVALS:
        raise ValueError(f"mix {name!r}: arrivals {mix['arrivals']!r} is not "
                         f"one of {ARRIVALS}; another arrival process is a "
                         f"change to this generator (README.md)")
    law = mix.get("tokens")
    if isinstance(law, dict) and law.get("dist") not in TOKEN_LAWS:
        raise ValueError(f"mix {name!r}: token law {law.get('dist')!r} is "
                         f"not one of {TOKEN_LAWS}; another law is a change "
                         f"to this generator (README.md)")
    return mix


def lognormal_lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped log-normal, ascending."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    inv = NormalDist().inv_cdf
    q = [(i + 0.5) / n for i in range(n)]
    raw = [spec["median"] * math.exp(spec["sigma"] * inv(p)) for p in q]
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, seconds: float) -> np.ndarray:
    """``n`` stratified quantiles of an exponential gap, ascending, scaled
    so that they sum to ``seconds``: ``n`` arrivals fall in [0, seconds)."""
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return gaps * (seconds / gaps.sum())


def even_order(n: int, step: float) -> np.ndarray:
    """The ranks 0..n-1 in a low-discrepancy order: position ``i`` gets the
    rank of frac((i + 1) x step) among all ``n``, so every stretch of
    consecutive positions holds ranks spread evenly over the whole."""
    frac = np.modf(np.arange(1, n + 1) * step)[0]
    return np.argsort(np.argsort(frac))


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def token_law(mix: Dict, vocab: int, seed: int
              ) -> Callable[[np.random.Generator, int], np.ndarray]:
    """How a request's ids are drawn.  ``"tokens"`` as a string (a note)
    means uniform over the vocabulary.  ``{"dist": "zipf", "exponent": s,
    "topics": k}``: the seed makes ``k`` orders of the vocabulary; a request
    draws one of them, then its ids by Zipf rank under it (rank ``r`` with
    weight ``r ** -s``), so requests of a topic share their hot ids — and a
    router that follows the ids is loaded unevenly."""
    law = mix.get("tokens")
    if not isinstance(law, dict):
        return lambda rng, n: rng.integers(0, vocab, n, dtype=np.int32)
    cdf = np.cumsum(np.arange(1, vocab + 1) ** -float(law["exponent"]))
    cdf /= cdf[-1]
    topics = [np.random.default_rng([int(seed), topic, 0])
              .permutation(vocab).astype(np.int32)
              for topic in range(int(law["topics"]))]

    def draw(rng: np.random.Generator, n: int) -> np.ndarray:
        order = topics[rng.integers(len(topics))]
        ranks = np.searchsorted(cdf, rng.random(n), side="right")
        return order[np.minimum(ranks, vocab - 1)]

    return draw


def _requests(prompts, outputs, due, draw, seed: int, block: int,
              first: int) -> List[TrafficRequest]:
    """One block's requests; the seed draws the tokens, by ``draw``."""
    rng = _rng(seed, block)
    return [TrafficRequest(f"r{first + i:06d}", draw(rng, int(p)),
                           int(o), float(t))
            for i, (p, o, t) in enumerate(zip(prompts, outputs, due))]


def open_loop_requests(mix: Dict, rate_rps: float, seconds: float,
                       vocab: int, seed: int) -> Iterator[TrafficRequest]:
    """Open loop without end: round(rate x seconds) requests due inside the
    window (block 0, the scored ones), and the same schedule again in every
    ``seconds`` after it."""
    n = max(1, int(round(rate_rps * seconds)))
    prompts = lognormal_lengths(mix["prompt"], n)[even_order(n, _PROMPT_STEP)]
    outputs = lognormal_lengths(mix["output"], n)[even_order(n, _OUTPUT_STEP)]
    outputs = np.minimum(outputs, mix["max_total"] - prompts)
    gaps = exponential_gaps(n, seconds)[even_order(n, _GAP_STEP)]
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    draw = token_law(mix, vocab, seed)
    block = 0
    while True:
        yield from _requests(prompts, outputs, due + block * seconds, draw,
                             seed, block, block * n)
        block += 1


def backlog_requests(mix: Dict, vocab: int, seed: int
                     ) -> Iterator[TrafficRequest]:
    """Every request due at 0, made lazily in stratified blocks, without
    end: the window's cut decides how many are used."""
    n = int(mix["backlog_block"])
    draw = token_law(mix, vocab, seed)
    block = 0
    while True:
        order = _rng(BACKLOG_ORDER_SEED, block)
        prompts = order.permutation(lognormal_lengths(mix["prompt"], n))
        outputs = order.permutation(lognormal_lengths(mix["output"], n))
        outputs = np.minimum(outputs, mix["max_total"] - prompts)
        yield from _requests(prompts, outputs, np.zeros(n), draw, seed,
                             block, block * n)
        block += 1


def requests(mix: Dict, rate_rps: Optional[float], seconds: float, vocab: int,
             seed: int) -> Iterator[TrafficRequest]:
    """The mix's requests in the order they are due, without end.  Those
    due before ``seconds`` are the window's own."""
    if mix["arrivals"] == "backlog":
        return backlog_requests(mix, vocab, seed)
    if not rate_rps or rate_rps <= 0:
        raise ValueError("an open-loop mix needs the cell's rate_rps")
    return open_loop_requests(mix, rate_rps, seconds, vocab, seed)
