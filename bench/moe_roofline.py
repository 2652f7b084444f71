"""Operations and bytes of a MoE layer's grouped expert matmul, from a
configuration's sizes and a call's token count.

The yardstick of ``sealed_gmm_roofline``: the same counts whatever groups,
pads or orders the work, so a later change to the kernel cannot move it.
For one (K, N) matmul of T tokens through the experts this chip holds:

* operations: 2 K N for each routed (token, expert) pair that lands on a
  held expert, T x top_k x held / total of them on average;
* bytes: every held expert's (K, N) weight read once, and the T tokens'
  activations in (K) and out (N), each at 2 bytes (the compute dtype).
"""
from __future__ import annotations

import re
from typing import List, Tuple

ITEMSIZE = 2
KERNEL = "sealed_gmm"
CALL = re.compile(r"=\s*\w+\[(\d+),(\d+),(\d+)\][^=]*?\bcustom-call\((.*)$")
SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def gmm_flops(c: dict, k: int, n: int, t: int) -> float:
    """Model operations of T tokens through one (K, N) expert matmul."""
    held, total = c["n_routed_experts"], c["n_routed_experts_total"]
    return 2.0 * k * n * t * c["num_experts_per_tok"] * held / total


def gmm_bytes(c: dict, k: int, n: int, t: int) -> float:
    """Bytes the call must move at least: held weights once, activations
    in and out."""
    return ITEMSIZE * (c["n_routed_experts"] * k * n + t * k + t * n)


def gmm_roofline_s(c: dict, k: int, n: int, t: int,
                   peak: dict) -> Tuple[float, str]:
    """Least time of the call on the chip, and which bound sets it."""
    t_flops = gmm_flops(c, k, n, t) / peak["peak_flops_bf16"]
    t_bytes = gmm_bytes(c, k, n, t) / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def knt(text: str):
    """(K, N, T) of a ``sealed_gmm`` custom call from its HLO text: the
    result (E, T, N), the slabs (E, T, K) and the weights (E, K, N) among
    its operands; None when the text is no such call."""
    call = CALL.search(text)
    if call is None:
        return None
    e, t, n = (int(call.group(i)) for i in (1, 2, 3))
    dims = [tuple(int(d) for d in s.split(","))
            for s in SHAPE.findall(call.group(4)) if s.count(",") == 2]
    for (e1, t1, k), (e2, k2, n2) in zip(dims, dims[1:]):
        if (e1, t1, e2, n2) == (e, t, e, n) and k == k2:
            return k, n, t
    return None


def calls(trace) -> List[Tuple[float, int, int, int]]:
    """(device seconds, K, N, T) of every ``sealed_gmm`` call in a
    ``bench.trace.Summary``. Only the kernel's own custom call counts, not
    the XLA operations its jitted wrapper lowers to."""
    out = []
    for dur, text in trace.kernel_events(KERNEL):
        shape = knt(text)
        if shape is not None:
            out.append((dur,) + shape)
    return out
