"""Device: share of the time inside the harness's ``step()`` spans in which
no operation ran on the chip, from the trace."""


def read(ctx):
    v = ctx.trace.idle_share("bench.step")
    return None if v is None else 100.0 * v
