"""End-to-end metrics from a host record, and the sample of finished
requests that the reference checks.

- ``ttft_p95_ms``: the 95th percentile over every request due in the
  window (open loop), from its due time to the stamp at which its first
  token reached the host.  A request that failed or never produced a token
  counts as missing: it is given the time from its due time to the last
  stamp of the run, which is less than its real wait.
- ``itl_p95_ms``: over every gap between consecutive tokens of every
  request, whose later token arrived in the window.  No per-request means.
- ``tokens_s``: prompt tokens of the requests whose first token arrived in
  the window plus every token that arrived in it, over the window.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from .drive import Record
from .stats import percentile

OK = "ok"


def ttft_ms(rec: Record) -> List[float]:
    return [((t.times[0] if t.times else rec.end) - t.due) * 1e3
            for t in rec.counted()]


def itl_ms(rec: Record) -> List[float]:
    out = []
    for t in rec.tracked:
        for a, b in zip(t.times, t.times[1:]):
            if rec.t0 <= b <= rec.t1:
                out.append((b - a) * 1e3)
    return out


def tokens_s(rec: Record) -> float:
    n = 0
    for t in rec.tracked:
        if t.times and rec.t0 <= t.times[0] <= rec.t1:
            n += t.prompt_len
        n += sum(1 for x in t.times if rec.t0 <= x <= rec.t1)
    return n / rec.seconds


def end_to_end(rec: Record, names) -> Dict[str, float]:
    """The named end-to-end metrics (all but setup_s) of this record."""
    fns = {"ttft_p95_ms": lambda: percentile(ttft_ms(rec), 95),
           "itl_p95_ms": lambda: percentile(itl_ms(rec), 95),
           "tokens_s": lambda: tokens_s(rec)}
    return {n: fns[n]() for n in names if n in fns}


def attempted_failed(rec: Record) -> Tuple[int, int]:
    counted = rec.counted()
    failed = sum(1 for t in counted
                 if (t.retired and t.req.outcome != OK) or not t.times)
    return len(counted), failed


def sample(rec: Record, seed: int, tokens: int, cap: int
           ) -> List[Tuple[np.ndarray, int]]:
    """(prompt + served tokens, prompt length) of finished requests: the
    one that served most tokens, then others drawn from the seed, until
    `tokens` served tokens or `cap` requests."""
    done = [t for t in rec.tracked if t.retired and t.req.outcome == OK]
    if not done:
        return []
    longest = max(done, key=lambda t: (len(t.req.tokens), t.prompt_len))
    rest = [t for t in done if t is not longest]
    order = np.random.default_rng([seed % (1 << 63), 3]).permutation(
        len(rest))
    picked, n = [longest], len(longest.req.tokens)
    for i in order:
        if n >= tokens or len(picked) >= cap:
            break
        picked.append(rest[i])
        n += len(rest[i].req.tokens)
    return [(np.concatenate([np.asarray(t.req.prompt, np.int32),
                             np.asarray(t.req.tokens, np.int32)]),
             t.prompt_len) for t in picked]
