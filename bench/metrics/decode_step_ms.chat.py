"""Step jits: device time of one decode step (the decode jit's XLA
module, first op to last) per call, over the traced window."""

from harness.layers import DECODE_MODULE

UNIT = "ms"


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.modules(DECODE_MODULE)
    if not calls:
        return None
    return sum(b - a for a, b in calls) / len(calls) * 1e-6
