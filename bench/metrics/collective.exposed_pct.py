"""Share of a step's device time in which a collective ran on a chip and
no other op of the step did, averaged over the chips; read where the
generator names its step in ``layer["step"]`` and the step exchanges
data between chips."""


def read(ctx):
    step = ctx.layer.get("step")
    if ctx.trace is None or step is None:
        return None
    return ctx.trace.exposed_collective_pct(step["module"])
