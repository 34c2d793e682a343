"""One general generator for every traffic file.

A traffic file (``bench/traffic/<name>.json``) gives the loop kind, the
rate or the number of clients, and the length distributions with their
bounds.  The program's own generators (``serving/api.py``) count arrivals
in engine steps, so a slower step would admit fewer requests per second;
here arrivals are in seconds of wall clock.

Every seed gets the same set of sizes and gaps: the lengths are the
distribution's quantiles at evenly spaced points.  In the closed loop the
seed permutes them and draws the token ids.  In the open loop the seed
draws only the token ids: the schedule (which size is due when) is the
same for every seed, because under open-loop load the order alone moves
the tails.  So runs with different seeds do the same work, and their
spread is the system's, not the generator's.

- ``"loop": "open"``: ``rate_rps`` requests a second.  A window of T
  seconds holds round(rate * T) requests; their gaps are the exponential
  distribution's quantiles, scaled to fill the window exactly.  An
  optional ``run_in_s`` sends the same mix for that many seconds before
  the window, so that the window opens on an engine already under its
  steady load.
- ``"loop": "closed"``: ``clients`` callers, each sending its next
  request when its last one finished.  Requests come from one stream in
  blocks of ``block`` sizes that each cover the distribution evenly.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, Iterator, List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Item:
    due: float               # seconds after the window opens (open loop)
    prompt: np.ndarray       # int32 token ids
    max_new: int


def quantile(dist: Dict, u: float) -> int:
    """The distribution's u-quantile, rounded and clipped to its bounds."""
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    elif dist["dist"] == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return int(min(max(round(x), lo), hi))


def even_sizes(dist: Dict, n: int, rng: np.random.Generator) -> List[int]:
    """n sizes at the quantiles (i + 1/2)/n, in an order the rng picks."""
    sizes = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    return [sizes[i] for i in rng.permutation(n)]


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 63), *stream])


def _arrivals(traffic: Dict, seed: int, stream: tuple, seconds: float,
              vocab: int, start: float) -> List[Item]:
    """round(rate * seconds) requests from `start` on: the schedule from
    a fixed stream, the token ids from the seed's."""
    sched, ids = _rng(0, *stream), _rng(seed, *stream)
    n = max(1, round(traffic["rate_rps"] * seconds))
    prompts = even_sizes(traffic["prompt"], n, sched)
    outputs = even_sizes(traffic["output"], n, sched)
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = gaps[sched.permutation(n)] * (seconds / gaps.sum())
    due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Item(float(d), ids.integers(0, vocab, size=p, dtype=np.int32), o)
            for d, p, o in zip(due, prompts, outputs)]


def open_loop(traffic: Dict, seed: int, seconds: float, vocab: int,
              block: int = 0) -> List[Item]:
    """The requests due in [block*seconds, (block+1)*seconds), in due
    order.  Block 0 is the measured window; later blocks keep the load on
    while the window's last requests finish."""
    return _arrivals(traffic, seed, (1, block), seconds, vocab,
                     block * seconds)


def run_in(traffic: Dict, seed: int, vocab: int) -> List[Item]:
    """The requests due in the ``run_in_s`` seconds before the window
    (their dues are negative), in due order; none without a run-in."""
    w = traffic.get("run_in_s", 0)
    return _arrivals(traffic, seed, (3,), w, vocab, -w) if w else []


def closed_stream(traffic: Dict, seed: int, vocab: int) -> Iterator[Item]:
    """The closed loop's requests in the order the clients take them."""
    k = traffic.get("block", 16)
    for b in range(1 << 30):
        rng = _rng(seed, 2, b)
        prompts = even_sizes(traffic["prompt"], k, rng)
        outputs = even_sizes(traffic["output"], k, rng)
        for p, o in zip(prompts, outputs):
            yield Item(0.0, rng.integers(0, vocab, size=p, dtype=np.int32), o)


def prompt_lengths(traffic: Dict) -> List[int]:
    """Every prompt length the mix can send (for the warmup's buckets)."""
    d = traffic["prompt"]
    return list(range(d["min"], d["max"] + 1))
