"""Elastic scaling: resume a run on a DIFFERENT mesh than it crashed on.

Checkpoints are host-numpy (checkpoint.manager), so rescaling is:
  1. build the new mesh from the surviving device set,
  2. re-derive param/opt PartitionSpecs for that mesh (rules are pure
     functions of (config, mesh)),
  3. device_put the restored host arrays with the new shardings.

``candidate_meshes`` enumerates the (data, model) factorizations of the
surviving chip count, preferring shapes that keep the model axis intact
(TP resharding moves the most bytes).
"""
from __future__ import annotations

from typing import List, Tuple

import jax

from repro.checkpoint.manager import CheckpointManager, rebuild_tree
from repro.config import ModelConfig
from repro.models import transformer as T
from repro.optim import adamw
from repro.sharding import rules


def candidate_meshes(n_devices: int, prefer_model: int = 16
                     ) -> List[Tuple[int, int]]:
    out = []
    for model in range(min(prefer_model, n_devices), 0, -1):
        if n_devices % model == 0:
            out.append((n_devices // model, model))
    return out


def rescale(cfg: ModelConfig, ckpt: CheckpointManager, devices=None,
            model_axis: int = 0):
    """Restore the latest checkpoint onto a mesh built from ``devices``.

    Returns (step, params, opt_state, mesh)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    cands = candidate_meshes(n)
    if model_axis:
        cands = [c for c in cands if c[1] == model_axis] or cands
    data, model = cands[0]
    mesh = jax.make_mesh((data, model), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2,
                         devices=devices[:data * model])

    step, host = ckpt.restore()
    pspec = T.param_spec(cfg)
    ospec = jax.eval_shape(adamw.init, pspec)
    p_sh = rules.to_named(mesh, rules.param_pspecs(cfg, mesh))
    o_sh = rules.to_named(mesh, rules.opt_pspecs(cfg, mesh))
    params = rebuild_tree(pspec, host["params"], p_sh)
    opt = rebuild_tree(ospec, host["opt"], o_sh) if "opt" in host else None
    return step, params, opt, mesh
