"""Whole decode step against the chip's bf16 peak: the model operations of
the tokens the decode ticks produced while the trace recorded (two per
matmul weight per token, and attention over each token's real context),
over the ticks' device time times the peak. Padding slots, sealing and
the cache's full-length view are work the model does not need, so they
count as time and not as operations."""
from bench import roofline


def read(ctx):
    runs = ctx.trace.programs.get("tick")
    lo, hi = ctx.run.traced or (None, None)
    if not runs or lo is None:
        return None
    flops = sum(roofline.decode_token_flops(ctx.config, c)
                for s in ctx.run.steps if s.start >= lo and s.end <= hi
                for c in s.decode_contexts)
    if not flops:
        return None
    return 100.0 * flops / (sum(runs) * ctx.peak["peak_flops_bf16"])
