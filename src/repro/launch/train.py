"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU container it runs reduced configs on a host mesh; on a real
cluster the same entrypoint runs the full config on the production mesh
(--production), with sealed checkpoints, heartbeats, and elastic resume.
"""
from __future__ import annotations

import argparse

import jax

from repro.config import SealConfig, TrainConfig
from repro.configs import get_config, get_reduced
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.runtime import compile_cache
from repro.runtime.fault import Heartbeat, StepWatchdog
from repro.train.loop import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--production", action="store_true",
                    help="full config on the 16x16 mesh (needs real devices)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--seal", default="coloe",
                    choices=["none", "direct", "counter", "coloe"])
    ap.add_argument("--smart-ratio", type=float, default=0.5)
    ap.add_argument("--log", default=None)
    ap.add_argument("--heartbeat-dir", default=None)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch) if args.production else get_reduced(args.arch)
    tc = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                     microbatches=args.microbatches,
                     checkpoint_every=args.checkpoint_every,
                     checkpoint_dir=args.checkpoint_dir,
                     warmup_steps=max(2, args.steps // 10))
    seal = SealConfig(mode=args.seal, smart_ratio=args.smart_ratio)
    if args.production:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        n = len(jax.devices())
        mesh = make_host_mesh(data=max(1, n // 2), model=min(2, n))
    hb = None
    if args.heartbeat_dir:
        hb = Heartbeat(args.heartbeat_dir, host_id=f"host{jax.process_index()}")
        hb.start()
    try:
        params, opt, metrics = train(
            cfg, tc, mesh, batch=args.batch, seq=args.seq, steps=args.steps,
            seal=seal if args.seal != "none" else None, log_path=args.log,
            watchdog=StepWatchdog(hard_limit_s=600))
        print({k: float(v) for k, v in metrics.items()})
    finally:
        if hb:
            hb.stop()


if __name__ == "__main__":
    main()
