"""Reductions of the program's own host spans (``fft.*``, ``repro.spans``)
in a traced window, for the readers of per-layer metrics with source
``program_span``. The spans come with the run's trace
(``xplane.Context.program_spans``); where the run was not traced, or the
program writes no such span (a commit from before it did), a reader
returns None.
"""

from __future__ import annotations

import statistics

from bench import intervals


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
