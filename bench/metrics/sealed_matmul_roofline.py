"""Kernel (``kernels/sealed_matmul.py``): the least time the chip could take
for the work of every ``sealed_matmul`` call in the trace, over the calls'
device time. Each call's work is an (M, K) x (K, N) product read off its
result and operand shapes, with every operand at the compute dtype (2
bytes), so it is the same work whatever implements it.

Only the kernel's own custom call counts: the XLA operations that its
jitted wrapper lowers to (padding the activations, the row mask's cast)
also bear its name, but they are not the kernel and carry no product."""
import re

from bench import roofline

KERNEL = "sealed_matmul"
CALL = re.compile(r"=\s*\w+\[(\d+),(\d+)\][^=]*?\bcustom-call\((.*)$")
SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def mkn(text: str):
    """(M, K, N) of a custom call from its HLO text: the result (M, N), the
    activations (M, K) and the weight (K, N) among its operands; None when
    the text is no such call."""
    call = CALL.search(text)
    if call is None:
        return None
    m, n = int(call.group(1)), int(call.group(2))
    dims = [tuple(int(d) for d in s.split(","))
            for s in SHAPE.findall(call.group(3)) if s.count(",") == 1]
    for (m2, k), (k2, n2) in zip(dims, dims[1:]):
        if (m2, n2) == (m, n) and k == k2:
            return m, k, n
    return None


def read(ctx):
    ideal = spent = 0.0
    for dur, text in ctx.trace.kernel_events(KERNEL):
        call = mkn(text)
        if call is None:
            continue
        ideal += roofline.matmul_roofline_s(*call, ctx.peak)[0]
        spent += dur
    return 100.0 * ideal / spent if spent else None
