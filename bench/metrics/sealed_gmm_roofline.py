"""Kernel (``kernels/sealed_gmm.py``): the least time the chip could take
for the work of every ``sealed_gmm`` call in the trace, over the calls'
device time. A call multiplies each held expert's slab of T rows, T being
the tokens of the step, by that expert's (K, N) weight; its work is fixed
by the configuration and T (``bench/moe_roofline.py``): the routed pairs
that land on held experts, and every held weight read once. K, N and T are
read off the call's operand and result shapes. In a trace with no such
call (a model without experts) the metric is absent."""
from bench import moe_roofline


def read(ctx):
    ideal = spent = 0.0
    for dur, k, n, t in moe_roofline.calls(ctx.trace):
        ideal += moe_roofline.gmm_roofline_s(ctx.config, k, n, t,
                                             ctx.peak)[0]
        spent += dur
    return 100.0 * ideal / spent if spent else None
