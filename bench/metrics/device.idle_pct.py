"""Share of the traced window in which no op ran on a chip, averaged over
the cell's chips: ``device.idle_pct.<cell kind>`` in every cell."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_pct()
