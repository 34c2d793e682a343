"""Engine spans on the profiler's clock.

``Spans.span(name, **args)`` is ``jax.profiler.TraceAnnotation``: a
context manager that records a host span with its arguments while a
profiler runs (``jax.profiler.trace`` / ``start_trace``) and costs about a
microsecond when none does.  The profiler is the only switch, so the
engine's spans are always emitted and land in the profiler's
``.xplane.pb`` (and its Perfetto export) on the same clock as the device
ops — a stretch in which the device sat idle can be read off against the
host phase that ran at the time.

The engine's spans, each nested in its parent on the calling thread:

  ``engine.step`` (``waiting``, ``running``)
    ``engine.schedule``        -- expiry, admission, preemption, batching
    ``engine.prefill`` (``rid``, ``tokens``, ``bucket``, ``hit``)
      ``engine.prefill.build`` / ``engine.sync_tables`` (``rows``) /
      ``engine.prefill.dispatch`` / ``engine.prefill.readback``
    ``engine.decode`` (``rows``, ``bucket``)
      ``engine.decode.build`` / ``engine.sync_tables`` /
      ``engine.decode.dispatch`` / ``engine.decode.readback`` /
      ``engine.decode.apply``
    ``engine.ragged`` (``tokens``) with ``engine.ragged.build`` /
      ``.dispatch`` / ``.readback`` / ``.apply`` (the ragged step)
    ``engine.retire`` / ``engine.observe``

``build`` makes the host arrays, ``dispatch`` uploads them and calls the
step jit, ``readback`` waits for the step's tokens, ``apply`` books them
into the requests.
"""

from __future__ import annotations


class Spans:
    """The span API the engine threads through its step."""

    def __init__(self):
        # imported here so that the metrics registry stays importable
        # without jax
        from jax.profiler import TraceAnnotation

        self.span = TraceAnnotation
