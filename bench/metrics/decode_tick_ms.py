"""Model step (``serve/step.py::make_decode_tick``): mean device time of one
execution of the decode-tick program, from the trace."""


def read(ctx):
    runs = ctx.trace.programs.get("tick")
    return sum(runs) / len(runs) * 1e3 if runs else None
