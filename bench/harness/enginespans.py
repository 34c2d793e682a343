"""Readings of the engine's own step spans against the device trace.

The engine wraps each ``step()`` in an ``engine.step`` span and each phase
of it in a child span (``engine.prefill``, ``engine.decode``,
``engine.sync_tables``, ... — ``repro/observability/trace.py`` lists
them), all ``jax.profiler`` annotations on the profiler's clock.  Where a
trace's ``host`` list holds them beside the ``bench.*`` spans, these
functions read how long the device sat idle inside the engine's steps and
how long a running decode batch waited for the prefills of the same step.
A trace without them reads None, as every reader does where it finds
nothing.
"""

from __future__ import annotations

import bisect
from typing import List, Optional

from .devtrace import Interval, Trace, clip, union


def spans(trace: Trace, name: str) -> List[Interval]:
    """The host spans named `name` that lie wholly inside the window."""
    a, b = trace.window
    return sorted((s, s + d) for n, s, d in trace.raw["host"]
                  if n == name and a <= s and s + d <= b)


def _busy(trace: Trace) -> List[Interval]:
    """The union of the op intervals on the first device, in the window."""
    ops = trace.devices[0]["ops"] if trace.devices else ()
    return union(iv for iv in (clip((s, s + d), trace.window)
                               for _, s, d in ops) if iv)


def _overlap(busy: List[Interval], a: float, b: float) -> float:
    """How much of (a, b) the sorted, disjoint `busy` intervals cover."""
    i = max(bisect.bisect_right(busy, (a, float("inf"))) - 1, 0)
    tot = 0.0
    for lo, hi in busy[i:]:
        if lo >= b:
            break
        tot += max(0.0, min(hi, b) - max(lo, a))
    return tot


def step_host_idle_ms(trace: Trace) -> Optional[float]:
    """Mean, over the ``engine.step`` spans in the window, of the time
    inside each in which no op ran on the first device."""
    steps = spans(trace, "engine.step")
    if not steps or not trace.devices:
        return None
    busy = _busy(trace)
    idle = sum((b - a) - _overlap(busy, a, b) for a, b in steps)
    return idle / len(steps) * 1e-6


def _within(ivs: List[Interval], a: float, b: float) -> List[Interval]:
    return [iv for iv in ivs if a <= iv[0] and iv[1] <= b]


def prefill_stall_ms(trace: Trace) -> Optional[float]:
    """Mean, over the ``engine.step`` spans in the window that hold both
    an ``engine.prefill`` and an ``engine.decode`` span, of the summed
    duration of their prefill spans: how long the running batch waited
    for the step's admissions."""
    prefills = spans(trace, "engine.prefill")
    decodes = spans(trace, "engine.decode")
    stalls = []
    for a, b in spans(trace, "engine.step"):
        mine = _within(prefills, a, b)
        if mine and _within(decodes, a, b):
            stalls.append(sum(e - s for s, e in mine))
    if not stalls:
        return None
    return sum(stalls) / len(stalls) * 1e-6
