"""Serving telemetry substrate (host-side only).

Three pillars, one bundle:

  * ``metrics``   -- counters / gauges / bounded-bucket histograms in a
                     ``MetricsRegistry``; Prometheus text exposition +
                     JSON snapshot (merged into engine ``stats()`` and the
                     serve.py report).
  * ``trace``     -- the engine's step spans, ``jax.profiler``
                     annotations on the profiler's clock: recorded beside
                     the device ops whenever a profiler runs (``serve
                     --trace-out DIR``, the benchmark's traced window).
  * ``jit_watch`` -- recompile sentinel: jit cache-miss deltas per step
                     function, tagged with the triggering bucket shape;
                     steady-state recompiles are a loud metric and an
                     optional hard failure (``strict``).

``Telemetry`` is the bundle the engine threads through the scheduler and
page pool.  ``metrics`` and ``jit_watch`` import no jax; ``trace`` imports
``jax.profiler`` when a bundle is built.  Nothing here is on the traced
path, so telemetry on vs off is token-identical by construction (asserted
end-to-end in tests/test_observability.py).
"""

from repro.observability.jit_watch import (  # noqa: F401
    NULL_JIT_WATCH,
    JitWatch,
    NullJitWatch,
    RecompileError,
)
from repro.observability.metrics import (  # noqa: F401
    COUNT_BUCKETS,
    NULL_REGISTRY,
    TIME_BUCKETS_US,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    global_registry,
)
from repro.observability.trace import Spans  # noqa: F401


class Telemetry:
    """The per-engine telemetry bundle: a metrics registry, the step span
    API, and a recompile sentinel.

    Defaults are production-shaped: metrics on (cheap host-side updates),
    the sentinel counting but not raising (``strict_recompiles=True`` turns
    a steady-state recompile into an exception — the tests' mode).  Spans
    have no switch here: they are recorded while a profiler runs and cost
    about a microsecond each otherwise.
    """

    def __init__(self, metrics: bool = True,
                 strict_recompiles: bool = False):
        self.registry = MetricsRegistry() if metrics else NULL_REGISTRY
        self.trace = Spans()
        self.jit_watch = (JitWatch(self.registry, strict=strict_recompiles)
                          if metrics else NULL_JIT_WATCH)

    @property
    def enabled(self) -> bool:
        return self.registry.enabled

    @classmethod
    def disabled(cls) -> "Telemetry":
        return cls(metrics=False)
