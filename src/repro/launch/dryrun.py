"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set the forced device count before ANY other import — jax locks the
device count on first init.
"""
import os
# a host-device compile tool: it must never take a TPU, so the CPU platform
# is pinned before JAX is imported
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import SHAPES, TrainConfig, cell_supported
from repro.configs import ARCH_IDS, get_config
from repro.launch import hlo_stats
from repro.launch.inputs import batch_specs, input_specs
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim import adamw
from repro.serve.step import make_decode_step
from repro.sharding import rules
from repro.sharding.api import use_mesh
from repro.train.step import make_prefill_step, make_train_step


def _dp_size(mesh):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("data", 1) * sizes.get("pod", 1)


def _replicated(mesh, tree):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             microbatches: int = 0, remat: str = "full",
             save_hlo: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = cell_supported(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "kind": shape.kind}
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = _dp_size(mesh)
    pspec = T.param_spec(cfg)
    p_sh = rules.to_named(mesh, rules.param_pspecs(
        cfg, mesh, serving=(shape.kind == "decode")))
    b_specs = batch_specs(cfg, shape, shape.kind)
    b_sh = rules.to_named(mesh, rules.batch_pspecs(cfg, mesh, shape.kind))
    # batch dims that do not divide dp (e.g. long_500k batch=1): replicate
    b_sh = jax.tree.map(
        lambda s, sh: NamedSharding(mesh, P(*([None] * len(s.shape))))
        if s.shape[0] % dp else sh, b_specs, b_sh)

    unknown_trip = 1
    if shape.kind == "train":
        mb = microbatches or max(1, min(shape.global_batch // dp, 16))
        tc = TrainConfig(microbatches=mb, remat=remat)
        rec["microbatches"] = mb
        step = make_train_step(cfg, tc)
        ospec = jax.eval_shape(adamw.init, pspec)
        o_sh = rules.to_named(mesh, rules.opt_pspecs(cfg, mesh))
        args = (pspec, ospec, b_specs)
        in_sh = (p_sh, o_sh, b_sh)
        donate = (0, 1)
        out_sh = None
    elif shape.kind == "prefill":
        # token-chunked MoE dispatch bounds prefill transients; batch
        # chunking is only a fallback (its cache-merge transpose costs more
        # than it saves — see EXPERIMENTS.md §Dry-run notes)
        chunks = 1
        rec["batch_chunks"] = chunks
        step = make_prefill_step(cfg, cache_len=shape.seq_len,
                                 batch_chunks=chunks)
        args = (pspec, b_specs)
        in_sh = (p_sh, b_sh)
        donate = ()
        out_sh = None
        unknown_trip = max(1, (shape.seq_len // 1024) // 2)  # causal kv loop
    else:  # decode
        step = make_decode_step(cfg)
        specs = input_specs(cfg, shape)
        c_sh = rules.to_named(mesh, rules.cache_pspecs(
            cfg, mesh, shape.global_batch, shape.seq_len))
        args = (pspec, specs["cache"], specs["batch"], specs["pos"])
        in_sh = (p_sh, c_sh, b_sh, NamedSharding(mesh, P()))
        donate = (1,)
        out_sh = None

    run_rules = rules.arch_rules(cfg, mesh)
    md = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if shape.kind == "train" and shape.seq_len % md == 0:
        # sequence-parallel residual stream (activation-memory lever)
        run_rules["seq_res"] = "model"
    with use_mesh(mesh, run_rules):
        jf = jax.jit(step, in_shardings=in_sh, donate_argnums=donate)
        lowered = jf.lower(*args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    stats = hlo_stats.module_totals(txt, unknown_trip_hint=unknown_trip)
    rec.update(
        status="ok",
        devices=mesh.devices.size,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        hlo_bytes=len(txt),
        flops_per_device=stats["flops"],
        bytes_per_device=stats["bytes"],
        flops_cost_analysis=float(ca.get("flops", 0.0)),
        bytes_accessed_cost=float(ca.get("bytes accessed", 0.0)),
        collective_bytes_per_device=stats["collectives"],
        unknown_trip_hint=unknown_trip,
        memory=dict(
            argument_bytes=ma.argument_size_in_bytes,
            output_bytes=ma.output_size_in_bytes,
            temp_bytes=ma.temp_size_in_bytes,
            alias_bytes=ma.alias_size_in_bytes,
        ) if ma is not None else None,
    )
    # loop-scaled estimate of bytes accessed (cost analysis counts loop
    # bodies once; scale by the parser's flop ratio)
    if ca.get("flops"):
        scale = max(1.0, stats["flops"] / float(ca["flops"]))
        rec["bytes_accessed_scaled"] = float(ca.get("bytes accessed", 0.0)) * scale
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(txt)
    print(compiled.memory_analysis())
    print({k: v for k, v in ca.items() if k in ("flops", "bytes accessed")})
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", default="")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    cells = []
    for a in ([args.arch] if args.arch else ARCH_IDS):
        for s in ([args.shape] if args.shape else list(SHAPES)):
            cells.append((a, s))
    if args.list:
        for a, s in cells:
            print(a, s)
        return

    os.makedirs(args.out, exist_ok=True)
    for a, s in cells:
        tag = f"{a}__{s}__{'mp' if args.multi_pod else 'sp'}"
        path = os.path.join(args.out, tag + ".json")
        try:
            rec = run_cell(a, s, args.multi_pod,
                           microbatches=args.microbatches, remat=args.remat,
                           save_hlo=args.save_hlo)
        except Exception as e:  # record failures, keep going
            rec = {"arch": a, "shape": s, "status": "error",
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        keys = ["arch", "shape", "mesh", "status"] + \
            (["compile_s"] if "compile_s" in rec else []) + \
            (["error"] if "error" in rec else [])
        print(json.dumps({k: rec[k] for k in keys}))


if __name__ == "__main__":
    main()
