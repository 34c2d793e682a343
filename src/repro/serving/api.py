"""Thin serving frontend: submit()/step()/collect() + synthetic traffic.

`poisson_trace` draws a reproducible open-loop request trace — exponential
interarrival times (in decode-step units, so scheduling decisions replay
identically across engines and KV layouts) with prompt/generation lengths
mixed over caller-provided choices.  `run_trace` feeds a trace through an
engine and returns the stats report; serve.py's benchmark and the
bit-exactness harness both sit on top of it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.serving.engine import InferenceEngine
from repro.serving.scheduler import Request, ShedError


@dataclasses.dataclass(frozen=True)
class TraceItem:
    arrival_step: int          # engine step at which the request arrives
    prompt: np.ndarray         # int32 [L]
    max_new: int


class ServingAPI:
    """submit/step/collect facade over the engine (the unit a network
    frontend would wrap; requests become visible immediately)."""

    def __init__(self, engine: InferenceEngine):
        self.engine = engine

    def submit(self, prompt, max_new: int, eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None) -> int:
        """Queue a request; ``deadline_s`` is a TTL relative to now
        (outcome=timeout once it passes).  Raises ShedError when the
        bounded admission queue is full."""
        return self.engine.submit(prompt, max_new, eos_id=eos_id,
                                  deadline_s=deadline_s)

    def cancel(self, rid: int) -> bool:
        """Cancel a queued, prefilling, or decoding request: pages release
        (shared prefix pages stay warm), the request retires with
        outcome=cancelled and surfaces via collect().  False when rid is
        unknown or already retired."""
        return self.engine.cancel(rid)

    def step(self) -> int:
        return self.engine.step()

    def collect(self) -> List[Request]:
        return self.engine.collect()

    def stats(self) -> Dict:
        return self.engine.stats()

    # ------------------------------------------------------- telemetry --
    def metrics_text(self) -> str:
        """Prometheus text exposition of the engine's registry — the body
        a network frontend's /metrics endpoint would serve."""
        return self.engine.metrics.render_text()


def poisson_trace(
    n_requests: int,
    rate_per_step: float,
    prompt_lens: Sequence[int],
    gen_lens: Sequence[int],
    vocab: int,
    seed: int = 0,
) -> List[TraceItem]:
    """Open-loop Poisson arrivals: interarrival ~ Exp(rate) in decode-step
    units; prompt/gen lengths drawn uniformly from the given choices."""
    rng = np.random.default_rng(seed)
    t, out = 0.0, []
    for _ in range(n_requests):
        t += rng.exponential(1.0 / max(rate_per_step, 1e-9))
        L = int(rng.choice(list(prompt_lens)))
        out.append(TraceItem(
            arrival_step=int(t),
            prompt=rng.integers(0, vocab, size=L, dtype=np.int32),
            max_new=int(rng.choice(list(gen_lens))),
        ))
    return out


def shared_prefix_trace(
    n_requests: int,
    rate_per_step: float,
    sys_len: int,
    user_lens: Sequence[int],
    gen_lens: Sequence[int],
    vocab: int,
    seed: int = 0,
    n_system_prompts: int = 1,
) -> List[TraceItem]:
    """Shared-system-prompt traffic: every request's prompt is one of
    `n_system_prompts` fixed system prefixes (`sys_len` tokens) followed by
    a unique user suffix — the workload where a prefix cache amortizes the
    system prompt's KV across the fleet.  Arrivals follow the same
    open-loop Poisson process as `poisson_trace`."""
    rng = np.random.default_rng(seed)
    systems = [rng.integers(0, vocab, size=sys_len, dtype=np.int32)
               for _ in range(max(1, n_system_prompts))]
    t, out = 0.0, []
    for _ in range(n_requests):
        t += rng.exponential(1.0 / max(rate_per_step, 1e-9))
        sys_p = systems[int(rng.integers(0, len(systems)))]
        user = rng.integers(0, vocab, size=int(rng.choice(list(user_lens))),
                            dtype=np.int32)
        out.append(TraceItem(
            arrival_step=int(t),
            prompt=np.concatenate([sys_p, user]),
            max_new=int(rng.choice(list(gen_lens))),
        ))
    return out


def mixed_trace(
    n_requests: int,
    prompt_lens: Sequence[int],
    gen_lens: Sequence[int],
    vocab: int,
    seed: int = 0,
) -> List[TraceItem]:
    """Batch-composition churn: exactly one arrival per decode step with
    prompt/gen lengths cycling through the cross product, so every step's
    running set mixes prefill chunks and decode tokens differently — the
    workload the ragged token-major step exists for (a bucketed engine
    re-pads every step; the ragged engine reuses one compiled shape)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_requests):
        L = int(prompt_lens[i % len(prompt_lens)])
        g = int(gen_lens[(i // len(prompt_lens)) % len(gen_lens)])
        prompt = rng.integers(0, vocab, size=L, dtype=np.int32)
        out.append(TraceItem(arrival_step=i, prompt=prompt, max_new=g))
    return out


def bursty_trace(
    n_requests: int,
    burst: int,
    period: int,
    prompt_lens: Sequence[int],
    gen_lens: Sequence[int],
    vocab: int,
    seed: int = 0,
) -> List[TraceItem]:
    """Bursty arrivals: groups of `burst` simultaneous requests every
    `period` decode steps (idle gaps between), alternating long-prompt and
    short-prompt bursts.  Stresses admission spikes — the bucketed engine
    pays one prefill launch per admission, the ragged engine drains the
    whole burst through its token budget."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_requests):
        group = i // burst
        L = int(prompt_lens[(group + i) % len(prompt_lens)])
        g = int(gen_lens[i % len(gen_lens)])
        prompt = rng.integers(0, vocab, size=L, dtype=np.int32)
        out.append(TraceItem(arrival_step=group * period, prompt=prompt,
                             max_new=g))
    return out


def run_trace(engine: InferenceEngine, trace: List[TraceItem],
              max_steps: int = 100_000) -> Tuple[Dict, List[Request]]:
    """Drive a trace to completion: submit each request at its arrival step,
    step until every request finished.  Returns (stats, finished requests
    sorted by rid)."""
    pending = sorted(trace, key=lambda it: it.arrival_step)
    finished: List[Request] = []
    i, step_idx = 0, 0
    while len(finished) < len(trace):
        if step_idx >= max_steps:
            raise RuntimeError(f"trace incomplete after {max_steps} steps")
        while i < len(pending) and pending[i].arrival_step <= step_idx:
            try:
                engine.submit(pending[i].prompt, pending[i].max_new)
            except ShedError:
                pass     # shed requests still retire through collect()
            i += 1
        engine.step()
        finished.extend(engine.collect())
        step_idx += 1
    return engine.stats(), sorted(finished, key=lambda r: r.rid)
