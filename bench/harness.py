"""What every generator shares: seeds, the window, compile counts,
memory.

A generator builds its cell in set-up, then runs its work inside
``Window``, which marks the measured interval (and traces it when the run
is traced), and returns an ``Outcome``.
"""

from __future__ import annotations

import gc
import glob
import os
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

#: the host span that marks the measured window in a trace
WINDOW_SPAN = "bench.window"
#: JAX's event around every executable it builds or loads from its cache
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class Cell:
    """One run of one workload, as ``bench/run.py`` hands it to a generator."""
    name: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    chips: int
    reference: object     # the configuration's plain reference module
    tmp: Path             # scratch directory outside the checkout


@dataclass
class Outcome:
    window_start: float                 # time.monotonic() at window start
    metrics: dict                       # end-to-end metric name -> value
    attempted: int
    failed: int
    checks: list                        # (name, value, limit)
    compiles_in_window: dict            # counter name -> count
    memory_peak_bytes: int
    trace_file: str | None = None
    layer: dict = field(default_factory=dict)   # inputs of the readers


def numpy_rng(seed: int, stream: int = 0) -> np.random.Generator:
    """Host randomness from the seed; ``stream`` separates its uses."""
    return np.random.default_rng([stream, seed])


def jax_key(seed: int):
    """A JAX key from a seed of any size (up to 2**63)."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


class CompileCounter:
    """Counts the executables JAX builds or loads while it is open."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1


def plan_traces() -> int:
    """Traces of every plan in the process (``ExecutablePlan.trace_counts``)."""
    from repro.fft import planner
    return sum(p.trace_count for p in list(planner._PLAN_CACHE.values()))


def steady() -> None:
    """Collect garbage and freeze what is left, so no collection in the
    window walks the objects set-up made."""
    gc.collect()
    gc.freeze()


def memory_peak(devices) -> int:
    """Peak device bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def evict(path) -> None:
    """Drop a file's pages from the page cache, so the next read is a
    read of the disk (the file must be written back already)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    finally:
        os.close(fd)


class Window:
    """The measured interval; traced into ``tmp`` when ``trace`` is set.

    ``start``/``end`` are ``time.monotonic()`` readings. Compiles and plan
    traces inside the window are counted in ``compiles``.
    """

    def __init__(self, trace: bool, tmp: Path, counter: CompileCounter):
        self.trace = trace
        self.dir = Path(tmp) / "trace"
        self.counter = counter
        self.start = self.end = None
        self.trace_file = None
        self.compiles = {}

    def __enter__(self):
        import jax
        self._c0 = (self.counter.count, plan_traces())
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._span.__enter__()
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc):
        import jax
        self.end = time.monotonic()
        if self.trace:
            self._span.__exit__(*exc)
            jax.profiler.stop_trace()
            files = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                              recursive=True)
            self.trace_file = files[0] if files else None
        self.compiles = {"backend_compiles": self.counter.count - self._c0[0],
                         "plan_traces": plan_traces() - self._c0[1]}
        return False

    @property
    def seconds(self) -> float:
        return self.end - self.start
