"""Operations and bytes of the serving path, from a configuration's sizes.

These are the yardstick for the per-layer metrics that divide work by
device time: the same counts whatever implements the work, so a later
change to the program cannot move them.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import List, Tuple


def peaks(device_kind: str, path: Path = None) -> dict:
    """The published peaks of one chip of ``device_kind``; a chip that is
    not in ``peaks.json`` is an error, never a default."""
    path = path or Path(__file__).with_name("peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(table)}")
    return table[device_kind]


def layer_matmuls(c: dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of every weight matmul of one decoder layer."""
    d, h, kv, hd, f = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    return [("wq", d, h * hd), ("wk", d, kv * hd), ("wv", d, kv * hd),
            ("wo", h * hd, d), ("wg", d, f), ("wi", d, f), ("wo_mlp", f, d)]


def head_matmul(c: dict) -> Tuple[str, int, int]:
    return ("head", c["hidden_size"], c["vocab_size"])


def matmul_params(c: dict) -> int:
    """Weights that take part in a matmul for every token: all layers'
    projections and the output head (the embedding's gather is no matmul,
    but a tied head is)."""
    per_layer = sum(k * n for _, k, n in layer_matmuls(c))
    _, k, n = head_matmul(c)
    return c["num_hidden_layers"] * per_layer + k * n


def attention_flops(c: dict, context: int) -> int:
    """Scores and weighted values of one query token over ``context``
    keys, all layers."""
    return (4 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * context)


def decode_token_flops(c: dict, context: int) -> int:
    """Model operations of one decoded token that attends to ``context``
    keys: two per matmul weight, and attention over the real length."""
    return 2 * matmul_params(c) + attention_flops(c, context)


def matmul_roofline_s(m: int, k: int, n: int, peak: dict,
                      itemsize: int = 2) -> Tuple[float, str]:
    """Least time of an (M, K) x (K, N) product on the chip, with every
    operand and the result at ``itemsize`` bytes (the compute dtype):
    the larger of operations over peak FLOP/s and bytes over peak
    bandwidth, and which of the two bounds it."""
    t_flops = 2.0 * m * k * n / peak["peak_flops_bf16"]
    t_bytes = itemsize * (k * n + m * k + m * n) / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
