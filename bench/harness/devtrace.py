"""The device trace: capture, reduction to intervals, and the readings
every per-layer reader shares.

A traced run brackets part of its window with ``jax.profiler`` and a
``bench.window`` host span.  ``reduce_xplane`` keeps, from the profiler's
``.xplane.pb``, each TPU plane's ``XLA Ops`` and ``XLA Modules`` events
and the host's ``bench.*`` spans, all on the profiler's one clock, as
plain lists ``[name, start_ns, duration_ns]``.  An op is named by its HLO
instruction (``w4a16_matmul.68``, ``fusion.12``): a Pallas kernel's
instruction carries the kernel's name.  A ``while`` op (the scan over
layers) holds the ops of its body, which the trace also lists, so it
counts towards nothing but the device's busy time.  A ``Trace`` answers
questions about that reduction; the tests run it on a trimmed copy of a
chip trace kept beside them, so every PR computes the same shares the
same way.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

#: ops that only hold other ops
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """``%w4a16_matmul.68 = f32[..] custom-call(..)`` -> ``w4a16_matmul.68``"""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def reduce_xplane(path: str) -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [[op_name(ev.name), ev.start_ns, ev.duration_ns]
                           for ev in line.events]
                elif line.name == "XLA Modules":
                    modules = [[ev.name, ev.start_ns, ev.duration_ns]
                               for ev in line.events]
            devices.append({"name": plane.name, "ops": ops,
                            "modules": modules})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, ev.start_ns, ev.duration_ns]
                            for ev in line.events
                            if ev.name.startswith("bench."))
    return {"devices": devices, "host": host}


class Capture:
    """Start and stop the profiler around part of a window; the trace goes
    to a temporary directory that ``read`` removes."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._span = None

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def read(self) -> "Trace":
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            return Trace(reduce_xplane(paths[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(iv: Interval, window: Interval) -> Optional[Interval]:
    a, b = max(iv[0], window[0]), min(iv[1], window[1])
    return (a, b) if b > a else None


class Trace:
    def __init__(self, reduced: Dict):
        self.raw = reduced
        spans = [s for s in reduced["host"] if s[0] == "bench.window"]
        if not spans:
            raise ValueError("trace holds no bench.window span")
        _, a, d = spans[0]
        self.window: Interval = (a, a + d)
        self.devices = [dv for dv in reduced["devices"] if dv["ops"]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _ops(self, dev) -> List[Tuple[str, Interval]]:
        out = []
        for name, start, dur in dev["ops"]:
            iv = clip((start, start + dur), self.window)
            if iv:
                out.append((name, iv))
        return out

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for dev in self.devices:
            tot += sum(b - a for a, b in union(iv for _, iv in
                                                self._ops(dev)))
        return tot * 1e-9 / len(self.devices)

    def modules(self, prefix: str) -> List[Interval]:
        """Executions (in the window) of the XLA modules whose name starts
        with `prefix`, on the first device."""
        out = []
        for name, a, d in self.devices[0]["modules"] if self.devices else ():
            if name.startswith(prefix):
                iv = clip((a, a + d), self.window)
                if iv and iv == (a, a + d):
                    out.append(iv)
        return out

    def kernel_s(self, names: Iterable[str],
                 within: Optional[List[Interval]] = None) -> float:
        """Seconds of the ops named after one of the kernels `names`
        (``<kernel>.<n>``), optionally only those inside the `within`
        intervals."""
        names = set(names)
        within = sorted(within) if within is not None else None
        tot = 0.0
        for name, (a, b) in self._ops(self.devices[0]) \
                if self.devices else ():
            if _base(name) not in names:
                continue
            if within is not None and not _inside(a, within):
                continue
            tot += b - a
        return tot * 1e-9

    def top_ops(self, n: int = 10) -> List[List]:
        """[name, seconds] of the ops that took most time in the window,
        grouped by name without its numeric suffix, containers left out."""
        acc: Dict[str, float] = {}
        for name, (a, b) in self._ops(self.devices[0]) \
                if self.devices else ():
            key = _base(name)
            if key not in CONTAINERS:
                acc[key] = acc.get(key, 0.0) + (b - a) * 1e-9
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """[host span, seconds] of the longest stretches of the window in
        which no op ran on the first device, each named by the innermost
        harness span around its middle ("none" outside every span)."""
        if not self.devices:
            return []
        busy = union(iv for _, iv in self._ops(self.devices[0]))
        edges = [self.window[0]] + [x for iv in busy for x in iv] \
            + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = [(s[1], s[1] + s[2], s[0]) for s in self.raw["host"]
                 if s[0] != "bench.window"]
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = (a + b) / 2
            inner = [s for s in spans if s[0] <= mid <= s[1]]
            name = min(inner, key=lambda s: s[1] - s[0])[2] if inner \
                else "none"
            out.append([name, (b - a) * 1e-9])
        return out


def _inside(t: float, sorted_ivs: List[Interval]) -> bool:
    import bisect

    i = bisect.bisect_right(sorted_ivs, (t, float("inf"))) - 1
    return i >= 0 and sorted_ivs[i][0] <= t <= sorted_ivs[i][1]


def _base(name: str) -> str:
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name
