"""Drive the engine with a traffic mix for a window of wall clock, and
stamp on the host what a client would see.

Every stamp is taken after ``engine.step()`` returns: each step ends by
reading its tokens back to the host, so a stamp taken then is after the
device.  A request's first token arrives at the stamp of the step that
produced it, and each later token at the stamp of its own step.

Besides the stamps, the record keeps per step what work it did (the
prefills with their prompt lengths, the decode rows with their context
lengths), from which the per-layer readers count operations and bytes.
With ``spans`` on, each call into the engine and each wait for the next
arrival is a ``jax.profiler.TraceAnnotation``, on the profiler's clock.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Callable, Dict, Iterator, List, Optional, Sequence

from .traffic import Item


@dataclasses.dataclass
class Tracked:
    req: object               # the engine's Request
    due: float                # host clock
    prompt_len: int
    max_new: int
    counted: bool             # due (open loop) or sent (closed) in the window
    times: List[float] = dataclasses.field(default_factory=list)
    retired: bool = False


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    prefills: List[int]       # prompt lengths prefilled in this step
    decode_ctx: List[int]     # per decode row: keys attended (context)
    live_kv: int              # tokens cached for the requests still running


@dataclasses.dataclass
class Record:
    start: float              # first request due (the run-in's, if any)
    t0: float                 # window opens (first request due in it)
    t1: float                 # window closes
    end: float                # last stamp taken (the drain included)
    tracked: List[Tracked]
    steps: List[Step]
    lateness: List[float]     # submit time minus due time, open loop
    counters0: Dict[str, int]
    counters1: Dict[str, int]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def counted(self) -> List[Tracked]:
        return [t for t in self.tracked if t.counted]

    def steps_in(self, a: float, b: float) -> List[Step]:
        return [s for s in self.steps if a <= s.t0 and s.t1 <= b]


COUNTERS = ("n_prefill_tokens", "n_decode_tokens", "n_tokens_packed",
            "n_tokens_wasted", "n_prefix_hit_tokens")


def _counters(engine) -> Dict[str, int]:
    c = {k: int(getattr(engine, k)) for k in COUNTERS}
    c["preemptions"] = int(engine.scheduler.n_preemptions)
    return c


class Driver:
    def __init__(self, engine, clock: Callable[[], float] = time.perf_counter,
                 spans: bool = False):
        self.engine = engine
        self.clock = clock
        self.spans = spans
        self.tracked: List[Tracked] = []
        self.active: List[Tracked] = []
        self.steps: List[Step] = []
        self.lateness: List[float] = []

    def span(self, name: str):
        if not self.spans:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def submit(self, item: Item, due: float, counted: bool) -> Tracked:
        eng = self.engine
        with self.span("bench.submit"):
            rid = eng.submit(item.prompt, item.max_new, arrival=due)
        req = eng.scheduler.waiting[-1]
        assert req.rid == rid, (req.rid, rid)
        t = Tracked(req, due, len(item.prompt), item.max_new, counted)
        self.tracked.append(t)
        self.active.append(t)
        return t

    def busy(self) -> bool:
        s = self.engine.scheduler
        return bool(s.running or s.waiting)

    def step(self) -> List[Tracked]:
        """One engine step; stamps its tokens; returns the requests that
        retired in it."""
        t0 = self.clock()
        with self.span("bench.step"):
            self.engine.step()
        now = self.clock()
        with self.span("bench.account"):
            self.engine.collect()
            prefills, decode_ctx, retired, still = [], [], [], []
            for t in self.active:
                have, n = len(t.times), len(t.req.tokens)
                if n > have:
                    if have == 0:
                        prefills.append(t.prompt_len)
                    # decode producing token j attends prompt_len + j keys
                    decode_ctx.extend(t.prompt_len + j
                                      for j in range(max(have, 1), n))
                    t.times.extend([now] * (n - have))
                if t.req.t_finish is not None:
                    t.retired = True
                    retired.append(t)
                else:
                    still.append(t)
            self.active = still
            live = sum(t.prompt_len + len(t.times) for t in still if t.times)
            self.steps.append(Step(t0, now, prefills, decode_ctx, live))
        return retired

    def wait_until(self, when: float) -> None:
        with self.span("bench.wait"):
            delay = when - self.clock()
            if delay > 0:
                time.sleep(delay)

    def record(self, start, t0, t1, counters0, counters1) -> Record:
        return Record(start, t0, t1, self.clock(), self.tracked, self.steps,
                      self.lateness, counters0, counters1)


Hooks = List[tuple]   # [(seconds after the window opens, fn)], in order


def _fire(hooks: Hooks, t0: float, now: float) -> None:
    while hooks and t0 + hooks[0][0] <= now:
        hooks.pop(0)[1]()


def run_open(engine, blocks: Callable[[int], List[Item]], seconds: float,
             drain_s: float = 60.0, spans: bool = False,
             hooks: Optional[Hooks] = None,
             run_in: Sequence[Item] = ()) -> Record:
    """Open loop: the `run_in` requests (negative dues) come first, so the
    window opens on a loaded engine; block 0 of the traffic is due in the
    window; later blocks keep the load on while the window's requests are
    still waiting for their first token (at most `drain_s` past the
    close).  `hooks` run at their times after the window opens, between
    steps."""
    d = Driver(engine, spans=spans)
    hooks = sorted(hooks or [], key=lambda h: h[0])
    c0, c1 = None, None
    items = blocks(0)
    start = d.clock()
    t0 = start - min([0.0] + [it.due for it in run_in])
    t1 = t0 + seconds
    queue = deque([(t0 + it.due, it, False) for it in run_in]
                  + [(t0 + it.due, it, True) for it in items])
    block = 1
    while True:
        now = d.clock()
        _fire(hooks, t0, now)
        if now >= t0 and c0 is None:
            c0 = _counters(engine)
        if now >= t1 and c1 is None:
            c1 = _counters(engine)
        while queue and queue[0][0] <= now:
            due, item, counted = queue.popleft()
            if counted:
                d.lateness.append(now - due)
            d.submit(item, due, counted)
        if now >= t1:
            waiting = any(not t.times for t in d.tracked if t.counted)
            if not waiting or now >= t1 + drain_s:
                break
            if not queue:
                queue.extend((t0 + it.due, it, False)
                             for it in blocks(block))
                block += 1
        if d.busy():
            d.step()
        elif queue:
            d.wait_until(min(queue[0][0], t1) if now < t1 else queue[0][0])
        elif now < t1:
            d.wait_until(min([t1] + [t0 + h[0] for h in hooks[:1]]))
    _fire(hooks, t0, float("inf"))
    return d.record(start, t0, t1, c0, c1)


def run_closed(engine, stream: Iterator[Item], clients: int, seconds: float,
               spans: bool = False, hooks: Optional[Hooks] = None) -> Record:
    """Closed loop: `clients` callers, each sending its next request as
    soon as its last one retired."""
    d = Driver(engine, spans=spans)
    hooks = sorted(hooks or [], key=lambda h: h[0])
    c0 = _counters(engine)
    first = [next(stream) for _ in range(clients)]
    t0 = d.clock()
    t1 = t0 + seconds
    for item in first:
        d.submit(item, t0, True)
    while True:
        now = d.clock()
        _fire(hooks, t0, now)
        if now >= t1:
            break
        for _ in d.step():
            now = d.clock()
            if now < t1:
                d.submit(next(stream), now, True)
    c1 = _counters(engine)
    _fire(hooks, t0, float("inf"))
    return d.record(t0, t0, t1, c0, c1)
