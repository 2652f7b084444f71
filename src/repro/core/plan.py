"""EncryptionPlan: apply the SE policy (paper §3.1) to a parameter pytree.

Classifies every leaf (by its path) into:
  * ``rows`` — weight matrices whose input rows are ℓ1-ranked; the top-r
    fraction is encrypted (r = SealConfig.smart_ratio);
  * ``full`` — tiny tensors (norm scales, biases, conv filters of the
    modality stubs, SSM scalars) that are always fully encrypted;
plus boundary protection: the embedding, the LM head, and the first/last
super-block of each layer stack are always fully encrypted (the LM analogue of the paper's
"first two CONV layers, last CONV, last FC" rule, §3.4.1).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import SealConfig
from repro.core.criticality import encryption_mask, row_importance


@dataclasses.dataclass
class LeafPlan:
    path: str
    mode: str                       # rows | full
    batch_axes: Tuple[int, ...]     # e.g. layer-stack / expert axes
    row_axes: Tuple[int, ...]
    mask: Optional[jnp.ndarray]     # (batch..., n_rows) bool; None for full
    total_bytes: int
    enc_bytes: int

    @property
    def enc_fraction(self) -> float:
        return self.enc_bytes / max(self.total_bytes, 1)


# path-suffix -> (batch_axes, row_axes) given leaf ndim. Leading axis 0 is
# always the layer-stack axis for block params.
def _classify(path: Tuple[str, ...], ndim: int):
    name = path[-1]
    parent = path[-2] if len(path) >= 2 else ""
    if name in ("wq", "wk", "wv"):
        return (0,), (1,)
    if parent == "attn" and name == "wo":
        return (0,), (1, 2)          # rows = (head, head_dim) inputs
    if parent == "attn" and name == "wk_b":
        return (0,), (1, 2)          # MLA: rows = (head, nope) inputs
    if parent == "attn" and name in ("wkv_a", "wk_rope", "wv_b"):
        return (0,), (1,)
    if parent in ("mlp", "experts") and name in ("wi", "wg", "wo"):
        if ndim == 4:                # MoE: (n, e, d_in, d_out)
            return (0, 1), (2,)
        return (0,), (1,)
    if parent == "shared" and name in ("wi", "wg", "wo"):
        return (0,), (1,)
    if name == "router":
        return (0,), (1,)
    if parent == "rec" and name in ("w_x", "w_gate", "w_rg", "w_ig", "w_out"):
        return (0,), (1,)
    if parent == "ssd" and name in ("w_in", "w_out"):
        return (0,), (1,)
    if path[0] == "embed" and name == "w":
        return (), (0,)
    if path[0] == "head" and name == "w":
        return (), (0,)
    return None                      # full


def _path_tuple(keypath) -> Tuple[str, ...]:
    out = []
    for k in keypath:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return tuple(out)


def make_plan(params, seal: SealConfig) -> Dict[str, LeafPlan]:
    """Build the per-leaf encryption plan. Runs on host (masks are small)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    plans: Dict[str, LeafPlan] = {}
    ratio = 1.0 if seal.mode == "none" else seal.smart_ratio
    for keypath, leaf in flat:
        path = _path_tuple(keypath)
        pstr = "/".join(path)
        nbytes = leaf.size * leaf.dtype.itemsize
        cls = _classify(path, leaf.ndim)
        boundary = seal.protect_boundary_layers and path[0] in ("embed", "head")
        if cls is None or ratio >= 1.0 or boundary:
            plans[pstr] = LeafPlan(pstr, "full", (), (), None, nbytes, nbytes)
            continue
        batch_axes, row_axes = cls
        imp = row_importance(leaf, row_axes, batch_axes)
        mask = encryption_mask(imp, ratio)
        if seal.protect_boundary_layers and \
                path[0] in ("blocks", "dense_blocks") and mask.ndim >= 1 \
                and batch_axes[:1] == (0,):
            # first & last super-block fully encrypted
            mask = mask.at[0].set(True)
            mask = mask.at[-1].set(True)
        frac = float(jnp.mean(mask.astype(jnp.float32)))
        plans[pstr] = LeafPlan(pstr, "rows", batch_axes, row_axes, mask,
                               nbytes, int(round(nbytes * frac)))
    return plans


def plan_totals(plans: Dict[str, LeafPlan]) -> Dict[str, float]:
    tot = sum(p.total_bytes for p in plans.values())
    enc = sum(p.enc_bytes for p in plans.values())
    return {"total_bytes": tot, "enc_bytes": enc,
            "enc_fraction": enc / max(tot, 1)}


def expand_mask(plan: LeafPlan, shape) -> jnp.ndarray:
    """Broadcast the row mask to the full leaf shape (True = encrypted)."""
    if plan.mask is None:
        return jnp.ones(shape, bool)
    # mask: (batch..., prod(row_axes)); un-flatten rows then broadcast
    row_shape = tuple(shape[a] for a in plan.row_axes)
    m = plan.mask.reshape(plan.mask.shape[:len(plan.batch_axes)] + row_shape)
    # m's dims correspond to batch_axes + row_axes (ascending in all our
    # registry entries); insert singleton dims at the reduced positions and
    # broadcast out.
    src_axes = tuple(plan.batch_axes) + tuple(plan.row_axes)
    out = m
    for a in range(len(shape)):
        if a not in src_axes:
            out = jnp.expand_dims(out, a)
    return jnp.broadcast_to(out, shape)
