"""Roofline share of a cell's transform step: the nominal work's least
time (``bench/work.py``) over the device time of every op of the step's
program, copies and collectives included. The generator names the
program and its work in ``layer["step"]``."""


def read(ctx):
    step = ctx.layer.get("step")
    if ctx.trace is None or step is None:
        return None
    return ctx.roofline_pct(step)
