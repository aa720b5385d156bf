"""Median host time of one planner launch in the traced window: the
program's ``fft.plan.launch`` spans that start inside it (shape checks,
the jit cache lookup, any H2D copy of host operands and the enqueue of
one call), in microseconds. None where the program writes no such span."""

from bench import programspans


def read(ctx):
    spans = ctx.program_spans
    if spans is None:
        return None
    return programspans.median_us(spans, "fft.plan.launch",
                                  ctx.trace.window)
