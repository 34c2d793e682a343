"""Step jits: device time of the prefill jit's XLA modules per 1000
unpadded prompt tokens, over the traced window."""

from harness.layers import PREFILL_MODULE

UNIT = "ms"


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.modules(PREFILL_MODULE)
    tokens = sum(sum(s.prefills) for s in run.traced_steps())
    if not calls or not tokens:
        return None
    return sum(b - a for a, b in calls) * 1e-6 / (tokens / 1000.0)
