"""Roofline analysis from dry-run records (EXPERIMENTS.md §Roofline).

Per (arch x shape) on the single-pod mesh:
  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = collective_bytes_per_device / (links x link_bw)
with the v5e peaks from ``repro.config.hw_peaks``. HLO_FLOPs come from the
loop-trip-scaled HLO parser (hlo_stats); HLO_bytes from cost_analysis
scaled by the same trip ratio; collective bytes from the parser.

MODEL_FLOPS = the useful math: 6*N_active*T for train, 2*N_active*T +
causal attention for prefill, 2*N_active*B + cache attention for decode.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro.config import SHAPES, ModelConfig, ShapeConfig, hw_peaks
from repro.configs import get_config

# the dry-run compiles for a pod of v5e chips on forced host devices, so
# the chip it models is named here rather than read from the host
TARGET_KIND = "TPU v5 lite"
# a v5e chip has 4 usable ICI links on a 2D torus; collective traffic is
# reported per device, so the effective egress bandwidth is links x bw.
ICI_LINKS = 4


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Useful-math FLOPs per step (global, all devices)."""
    n_active = cfg.param_count(active_only=True)
    n_embed = cfg.vocab_size * cfg.d_model
    n_matmul = n_active - n_embed          # embedding gather is not a matmul
    kinds = cfg.layer_kinds()
    n_attn_layers = sum(1 for k in kinds if k == "attn")
    n_local_layers = sum(1 for k in kinds if k == "local_attn")
    hd = cfg.num_heads * cfg.head_dim

    if shape.kind == "train":
        toks = shape.seq_len * shape.global_batch
        base = 6.0 * n_matmul * toks
        # attention scores+values, causal half, fwd(2) + bwd(4)
        attn = 6.0 * shape.global_batch * hd * (
            n_attn_layers * shape.seq_len ** 2 / 2
            + n_local_layers * shape.seq_len * min(cfg.window or shape.seq_len,
                                                   shape.seq_len) / 1)
        return base + attn
    if shape.kind == "prefill":
        toks = shape.seq_len * shape.global_batch
        base = 2.0 * n_matmul * toks
        attn = 2.0 * shape.global_batch * hd * (
            n_attn_layers * shape.seq_len ** 2 / 2
            + n_local_layers * shape.seq_len * min(cfg.window or shape.seq_len,
                                                   shape.seq_len))
        return base + attn
    # decode: one token per sequence against the cache
    base = 2.0 * n_matmul * shape.global_batch
    cache = shape.seq_len
    attn = 2.0 * shape.global_batch * hd * (
        n_attn_layers * cache
        + n_local_layers * min(cfg.window or cache, cache)) * 2
    return base + attn


def roofline_row(rec: dict) -> Optional[dict]:
    if rec.get("status") != "ok":
        return None
    cfg = get_config(rec["arch"])
    shape = SHAPES[rec["shape"]]
    flops_dev = rec["flops_per_device"]
    bytes_dev = rec.get("bytes_per_device",
                        rec.get("bytes_accessed_scaled", 0.0))
    coll_dev = sum(rec["collective_bytes_per_device"].values())
    hw = hw_peaks(TARGET_KIND)
    t_comp = flops_dev / hw["peak_flops_bf16"]
    t_mem = bytes_dev / hw["hbm_bw"]
    t_coll = coll_dev / (ICI_LINKS * hw["ici_bw"])
    dom = max((t_comp, "compute"), (t_mem, "memory"), (t_coll, "collective"))
    mf = model_flops(cfg, shape)
    hlo_global = flops_dev * rec["devices"]
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "bottleneck": dom[1],
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        # roofline fraction: useful work rate vs peak if the dominant term
        # were fully utilized
        "roofline_fraction": (mf / rec["devices"] / hw["peak_flops_bf16"]) /
                             max(dom[0], 1e-30),
        "collectives": rec["collective_bytes_per_device"],
        "memory_gib": ((rec["memory"]["temp_bytes"] +
                        rec["memory"]["argument_bytes"]) / 2**30
                       if rec.get("memory") else None),
    }


def build_table(result_dir: str = "results/dryrun", mesh: str = "16x16"
                ) -> List[dict]:
    rows = []
    for f in sorted(glob.glob(os.path.join(result_dir, "*.json"))):
        rec = json.load(open(f))
        if rec.get("mesh") != mesh:
            continue
        row = roofline_row(rec)
        if row:
            rows.append(row)
    return rows


def render_markdown(rows: List[dict]) -> str:
    hdr = ("| arch | shape | compute s | memory s | collective s | bound | "
           "MODEL/HLO | roofline frac | mem GiB |\n"
           "|---|---|---|---|---|---|---|---|---|\n")
    out = [hdr]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3e} | "
            f"{r['t_memory_s']:.3e} | {r['t_collective_s']:.3e} | "
            f"{r['bottleneck']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']:.2f} | {r['memory_gib']:.1f} |\n")
    return "".join(out)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--json", action="store_true")
    a = ap.parse_args()
    rows = build_table(a.dir, a.mesh)
    if a.json:
        print(json.dumps(rows, indent=1))
    else:
        print(render_markdown(rows))
