"""Model step (``serve/step.py::make_chunk_step``): mean device time of one
execution of the chunked-prefill program, from the trace."""


def read(ctx):
    runs = ctx.trace.programs.get("chunk_step")
    return sum(runs) / len(runs) * 1e3 if runs else None
