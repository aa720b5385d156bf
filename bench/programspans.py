"""The program's own host spans (``fft.*``, ``repro.spans``) in a traced
window, for the readers of per-layer metrics with source ``program_span``.

``xplane.load`` keeps the benchmark's ``bench.*`` spans only, so these
readers read the program's from the same ``.xplane.pb``: ``run.py`` keeps
it in its scratch directory (``<tmp>/bench_*/trace/``) until the readers
have run, and the run's file is the newest one there, confirmed by its
``bench.window`` span being the context's window. Where there is no such
file, or the program writes no such span (a commit from before it did),
a reader returns None.
"""

from __future__ import annotations

import functools
import glob
import os
import statistics
import tempfile

from bench import intervals
from bench.harness import WINDOW_SPAN
from bench.xplane import Span

#: host spans the program writes
PREFIX = "fft."


@functools.lru_cache(maxsize=1)
def host_spans(path: str, mtime_ns: int) -> tuple:
    """The window span and the program's spans of one ``.xplane.pb``
    (``mtime_ns`` keys the cache)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return tuple(Span(e.name, e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9)
                 for plane in pd.planes if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name.startswith((PREFIX, WINDOW_SPAN)))


def of(ctx) -> list | None:
    """The program's spans in ``ctx``'s traced run; None when the run was
    not traced or its trace file is not found."""
    if ctx.trace is None:
        return None
    files = glob.glob(os.path.join(tempfile.gettempdir(), "bench_*", "trace",
                                   "**", "*.xplane.pb"), recursive=True)
    if not files:
        return None
    newest = max(files, key=os.path.getmtime)
    found = host_spans(newest, os.stat(newest).st_mtime_ns)
    if [(s.start, s.end) for s in found
            if s.name == WINDOW_SPAN] != [tuple(ctx.trace.window)]:
        return None
    return [s for s in found if s.name.startswith(PREFIX)]


def median_us(spans: list, name: str, window: tuple):
    """Median duration in microseconds of the ``name`` spans that start
    inside ``window``; None where none does."""
    lo, hi = window
    d = [s.end - s.start for s in spans if s.name == name
         and lo <= s.start < hi]
    return 1e6 * statistics.median(d) if d else None


def union_pct(spans: list, name: str, window: tuple):
    """Share of ``window`` covered by the union of the ``name`` spans; None
    where the program wrote none at all."""
    mine = [(s.start, s.end) for s in spans if s.name == name]
    if not mine:
        return None
    lo, hi = window
    return 100.0 * intervals.length(intervals.clip(mine, lo, hi)) / (hi - lo)
