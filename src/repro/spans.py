"""Host spans on the profiler's clock, and the stage clocks they feed.

``span("fft.<layer>.<what>", **attrs)`` is a ``jax.profiler.TraceAnnotation``:
inside a profiler session it is a host event on the same clock as the
device's ops, with ``attrs`` as its stats; with no session it costs about
a microsecond. ``timed`` is the same span that also adds its seconds to
``totals[key]`` (a ``JobStats.stage_s`` or ``wait_s``), so a stage clock
and the trace's spans are one measurement. The profiler session is the
only switch.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

from jax.profiler import TraceAnnotation as span

__all__ = ["span", "timed"]


@contextmanager
def timed(name: str, totals: dict, key: str, lock: threading.Lock,
          **attrs):
    """``span(name, **attrs)`` whose duration, failed calls included, is
    added to ``totals[key]`` under ``lock``."""
    with span(name, **attrs):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with lock:
                totals[key] = totals.get(key, 0.0) + dt
