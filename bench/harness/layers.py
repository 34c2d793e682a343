"""What the per-layer readers share: the run they read, and the loaders
that find a metric's reader and a kernel's work module by name.

A reader is ``bench/metrics/<metric>.py`` with a ``UNIT`` and a
``read(run)`` that returns a number, or None where the run holds nothing
for it to read (then the line leaves the metric out).  A work module is
``bench/work/<kernel>.py``: ``calls(m, rows, ctx)`` gives the (operations,
bytes) of each call one step makes, for a step of `rows` rows (the decode
bucket, or one prefill's padded prompt) whose decode rows attend `ctx`
keys each (None for a prefill).

The device trace is read by the names the chip's trace already gives: the
XLA module of each step jit (named after the program's step functions)
and the kernel functions inside them.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
from typing import Dict, Iterator, List, Optional, Tuple

from .drive import Record, Step
from .spec import BENCH_DIR

#: XLA module names of the engine's step jits (launch/steps.py)
DECODE_MODULE = "jit_dec_step"
PREFILL_MODULE = "jit_prefill_step"


@dataclasses.dataclass
class Run:
    m: Dict                    # the configuration's model sizes
    sv: object                 # the engine's ServingConfig
    record: Record
    peaks: Dict                # this device's row of bench/peaks.json
    trace: Optional[object] = None          # devtrace.Trace
    traced: Optional[Tuple[float, float]] = None   # host clock bounds

    def traced_steps(self) -> List[Step]:
        return self.record.steps_in(*self.traced) if self.traced else []


def load_metric(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_work(name: str):
    return importlib.import_module(f"work.{name}")


def _roof(calls: Iterator[Tuple[float, float]], peaks: Dict) -> float:
    """Least seconds the chip could take for these calls."""
    return sum(max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_s"])
               for f, b in calls)


def decode_calls(run: Run, kernel: str) -> Iterator[Tuple[float, float]]:
    w = load_work(kernel)
    for s in run.traced_steps():
        if not s.decode_ctx:
            continue
        yield from w.calls(run.m, run.sv.decode_bucket(len(s.decode_ctx)),
                           s.decode_ctx)


def prefill_calls(run: Run, kernel: str) -> Iterator[Tuple[float, float]]:
    w = load_work(kernel)
    for s in run.traced_steps():
        for length in s.prefills:
            yield from w.calls(run.m, run.sv.prompt_bucket(length), None)


def roofline_share(run: Run, kernel: str, regime: str) -> Optional[float]:
    """The kernel's share of its roofline over its calls in the traced
    window's `regime` ("decode" or "prefill") steps: the least time the
    chip could take for their work, over the time their ops took."""
    if run.trace is None or run.peaks is None:
        return None
    module = DECODE_MODULE if regime == "decode" else PREFILL_MODULE
    calls = (decode_calls if regime == "decode" else prefill_calls)(
        run, kernel)
    roof = _roof(calls, run.peaks)
    took = run.trace.kernel_s(load_work(kernel).TRACE_NAMES,
                              within=run.trace.modules(module))
    if roof <= 0 or took <= 0:
        return None
    return 100.0 * roof / took


def idle_share(run: Run) -> Optional[float]:
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)


def mfu(run: Run) -> Optional[float]:
    """Operations the model requires for the tokens of the traced window's
    steps (prompts unpadded), over the window at the bfloat16 peak."""
    if run.trace is None or run.peaks is None:
        return None
    model = load_work("model")
    flops = 0.0
    for s in run.traced_steps():
        flops += sum(model.prefill_flops(run.m, n) for n in s.prefills)
        flops += sum(model.decode_flops(run.m, c) for c in s.decode_ctx)
    if flops <= 0:
        return None
    return 100.0 * flops / (run.trace.window_s * run.peaks["bf16_flops"])
