"""Kernel (``kernels/sealed_gmm.py``): device time of the expert path per
decode step — the ``sealed_gmm`` calls of the decode ticks traced (those
whose slabs hold one row per slot), over the number of ``tick`` executions
in the trace. Absent where the trace holds no tick or no such call.

A tick's calls are told from a chunk step's by their rows alone: a chunk
step's slabs hold ``admit_batch`` x ``chunk_tokens`` rows (8 x 32 = 256 at
32 slots), a tick's one per slot. ``bench.trace.Summary`` keeps each
program's durations but not its intervals, so the call cannot yet be
placed inside the ``jit_tick`` execution that holds it."""
from bench import moe_roofline


def read(ctx):
    ticks = ctx.trace.programs.get("tick")
    if not ticks:
        return None
    spent = [dur for dur, _, _, t in moe_roofline.calls(ctx.trace)
             if t == ctx.config["slots"]]
    return sum(spent) / len(ticks) * 1e3 if spent else None
