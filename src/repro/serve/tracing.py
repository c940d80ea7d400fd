"""Engine spans: one context manager at each of the engine's layer
boundaries.

``span(stats, name, **meta)`` enters a profiler annotation named
``serve.<name>`` and adds its host duration to ``stats["span_s.<name>"]``
and 1 to ``stats["span_n.<name>"]``.  While a profiler session runs
(``jax.profiler.start_trace``), the annotation lands on the host plane of
the same trace as the device's operations, so a device-idle interval can
be charged to the innermost span that covers it; ``meta`` (``rid``,
``slots``, ``tokens``) arrives there as the event's stats.  With no
session the annotation costs about a microsecond and the counters are the
whole record.
"""
from __future__ import annotations

import contextlib
import time

from jax.profiler import TraceAnnotation

PREFIX = "serve."


@contextlib.contextmanager
def span(stats: dict, name: str, **meta):
    t = time.perf_counter()
    try:
        with TraceAnnotation(PREFIX + name, **meta):
            yield
    finally:
        key = f"span_s.{name}"
        stats[key] = stats.get(key, 0.0) + time.perf_counter() - t
        key = f"span_n.{name}"
        stats[key] = stats.get(key, 0) + 1
