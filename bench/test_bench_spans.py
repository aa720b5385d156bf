"""CPU tests of the readers of the program's own spans
(``bench/programspans.py``): the numbers they reduce a window to, the
spans of a real profiler trace reaching them through the run's context,
and None where there is nothing to read."""

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


def _context(window, spans=()):
    trace = xplane.Trace(devices={0: []},
                         spans=[_span(WINDOW_SPAN, *window), *spans],
                         window=window)
    return xplane.Context(trace=trace, layer={}, device_kind="TPU v5 lite")


def _record(tmp_path, program_spans: bool):
    """A profiler trace of a window that holds three launches and both
    dispatcher waits, read back as ``xplane.load`` reads its host spans;
    the context of that window."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
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
            with jax.profiler.TraceAnnotation("other.span"):
                time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    (path,) = (tmp_path / "trace").glob("**/*.xplane.pb")
    spans = xplane.host_spans(ProfileData.from_file(str(path)))
    (window,) = [s for s in spans if s.name == WINDOW_SPAN]
    return _context((window.start, window.end),
                    [s for s in spans if s is not window])


def test_readers_find_the_runs_trace(tmp_path):
    """The program's spans of a real trace reach the readers through the
    context, and the readers reduce them to what the spans themselves
    read: the median launch and each wait's share of the window, however
    long the host took to run them."""
    ctx = _record(tmp_path, program_spans=True)
    spans = ctx.program_spans
    assert sorted({s.name for s in spans}) == [
        "fft.plan.launch", "fft.stream.wait_decoded",
        "fft.stream.wait_inflight"]
    launch_us, decoded, inflight = (discover.load_reader(m)(ctx)
                                    for m in READERS)

    def lengths(name):
        return [s.end - s.start for s in spans if s.name == name]

    assert len(lengths("fft.plan.launch")) == 3
    assert launch_us == pytest.approx(
        1e6 * sorted(lengths("fft.plan.launch"))[1])
    for share, name in ((decoded, "fft.stream.wait_decoded"),
                        (inflight, "fft.stream.wait_inflight")):
        (length,) = lengths(name)
        assert 0 < share == pytest.approx(100.0 * length / ctx.window_s)
    # the same spans seen from another window: nothing starts inside it
    other = _context((ctx.trace.window[1] + 1.0, ctx.trace.window[1] + 2.0),
                     spans)
    assert discover.load_reader(READERS[0])(other) is None
    assert discover.load_reader(READERS[1])(other) == 0.0


def test_readers_read_nothing_where_the_program_wrote_no_span(tmp_path):
    ctx = _record(tmp_path, program_spans=False)
    assert ctx.program_spans == []
    assert all(discover.load_reader(m)(ctx) is None for m in READERS)


def test_readers_read_nothing_untraced():
    ctx = xplane.Context(trace=None, layer={}, device_kind="TPU v5 lite")
    assert ctx.program_spans is None
    assert all(discover.load_reader(m)(ctx) is None for m in READERS)
    # traced, but only the benchmark's own spans
    assert all(discover.load_reader(m)(_context(
        (0.0, 1.0), [_span("bench.wait", 0.1, 0.2)])) is None
        for m in READERS)
