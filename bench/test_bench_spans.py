"""CPU tests of the readers of the program's own spans
(``bench/programspans.py``): the numbers they reduce a window to, how
they find the run's trace file, and None where there is nothing to read."""

import time

import jax
import pytest

from bench import discover, programspans, xplane
from bench.harness import WINDOW_SPAN

READERS = ("plan.launch_us.batch", "stream.wait_decoded_pct.file",
           "stream.wait_inflight_pct.file")


def _span(name, start, end):
    return xplane.Span(name, start, end)


def test_median_and_union_of_program_spans():
    window = (10.0, 20.0)
    spans = [_span("fft.plan.launch", 9.0, 9.5),        # starts before
             _span("fft.plan.launch", 11.0, 11.00002),
             _span("fft.plan.launch", 12.0, 12.00004),
             _span("fft.plan.launch", 13.0, 13.00009),
             _span("fft.stream.wait_decoded", 9.0, 11.0),  # 1 s inside
             _span("fft.stream.wait_decoded", 15.0, 16.0),
             _span("fft.stream.wait_decoded", 15.5, 16.5),  # overlaps
             _span("fft.stream.wait_decoded", 19.5, 21.0)]  # 0.5 s inside
    assert programspans.median_us(spans, "fft.plan.launch",
                                  window) == pytest.approx(40.0)
    assert programspans.union_pct(spans, "fft.stream.wait_decoded",
                                  window) == pytest.approx(30.0)
    # written, but not in this window: none waited
    assert programspans.union_pct(
        spans, "fft.stream.wait_decoded", (30.0, 40.0)) == 0.0
    # never written (a program without the span): nothing to read
    assert programspans.union_pct(spans, "fft.stream.wait_inflight",
                                  window) is None
    assert programspans.median_us(spans, "fft.plan.launch",
                                  (30.0, 40.0)) is None


def _context(window):
    trace = xplane.Trace(devices={0: []},
                         spans=[_span(WINDOW_SPAN, *window)], window=window)
    return xplane.Context(trace=trace, layer={}, device_kind="TPU v5 lite")


def _record(tmp_path, monkeypatch, program_spans: bool):
    """A profiler trace where ``bench/run.py`` leaves it, with a window
    that holds three launches and both dispatcher waits; the window read
    from the file as ``xplane.load`` reads it."""
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "bench_x" / "trace"),
                             profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            if program_spans:
                for _ in range(3):
                    with jax.profiler.TraceAnnotation("fft.plan.launch",
                                                      plan="c2c_8_b1_ref"):
                        time.sleep(0.002)
                with jax.profiler.TraceAnnotation("fft.stream.wait_decoded"):
                    time.sleep(0.02)
                with jax.profiler.TraceAnnotation(
                        "fft.stream.wait_inflight"):
                    time.sleep(0.01)
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    ctx = _context((0.0, 1.0))
    (path,) = (tmp_path / "bench_x" / "trace").glob("**/*.xplane.pb")
    (window,) = [s for s in programspans.host_spans(
        str(path), path.stat().st_mtime_ns) if s.name == WINDOW_SPAN]
    return _context((window.start, window.end)), ctx


def test_readers_find_the_runs_trace(tmp_path, monkeypatch):
    ctx, other = _record(tmp_path, monkeypatch, program_spans=True)
    launch_us, decoded, inflight = (discover.load_reader(m)(ctx)
                                    for m in READERS)
    assert 2000 <= launch_us < 2000 + 5000
    assert 0 < inflight < decoded < 100
    share = 100.0 * 0.02 / ctx.window_s
    assert decoded == pytest.approx(share, rel=0.2)
    # the newest trace file is another window's: nothing is read from it
    assert all(discover.load_reader(m)(other) is None for m in READERS)


def test_readers_read_nothing_where_the_program_wrote_no_span(
        tmp_path, monkeypatch):
    ctx, _ = _record(tmp_path, monkeypatch, program_spans=False)
    assert all(discover.load_reader(m)(ctx) is None for m in READERS)


def test_readers_read_nothing_untraced(tmp_path, monkeypatch):
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    ctx = xplane.Context(trace=None, layer={}, device_kind="TPU v5 lite")
    assert all(discover.load_reader(m)(ctx) is None for m in READERS)
    # traced, but no trace file where the run keeps it
    assert all(discover.load_reader(m)(_context((0.0, 1.0))) is None
               for m in READERS)
