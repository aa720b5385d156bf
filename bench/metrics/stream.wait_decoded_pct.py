"""Share of the traced window in which the stream's dispatcher waited on
the decoded queue: the union of the program's ``fft.stream.wait_decoded``
spans over the window. High when the readers set the pace. None where the
program writes no such span."""

from bench import programspans


def read(ctx):
    spans = ctx.program_spans
    if spans is None:
        return None
    return programspans.union_pct(spans, "fft.stream.wait_decoded",
                                  ctx.trace.window)
