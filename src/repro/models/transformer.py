"""Top-level LM: init / forward (train) / prefill / decode.

Layers are stacked per pattern-position and iterated with ``jax.lax.scan``
over super-blocks, so HLO size and compile time are O(1) in depth — this is
what keeps the 512-device dry-runs tractable for 62-layer models.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig
from repro.models import blocks as B
from repro.models import layers as L
from repro.models.cache import model_cache_init, model_cache_spec
from repro.sharding.api import constrain


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key):
    """Random parameters from ``key``. Compiled, so the layer stacks are
    written in place: built eagerly, the per-layer arrays and their stack
    would briefly hold the whole model twice."""
    n = cfg.n_superblocks()
    ke, kh, kf, kb = jax.random.split(key, 4)
    params = {
        "embed": {"w": (jax.random.normal(ke, (cfg.vocab_size, cfg.d_model))
                        * cfg.d_model ** -0.5).astype(jnp.float32)},
        "final_norm": L.init_norm(cfg, kf),
    }
    if not cfg.tie_embeddings:
        params["head"] = {"w": (jax.random.normal(kh, (cfg.d_model, cfg.vocab_size))
                                * cfg.d_model ** -0.5).astype(jnp.float32)}
    def stack(layers, dense=False):
        per_position = []
        for j, kind in enumerate(cfg.pattern):
            stacked = [B.init_block(cfg, kind,
                                    jax.random.fold_in(kb, i * 131 + j),
                                    dense=dense)
                       for i in layers]
            per_position.append(jax.tree.map(lambda *xs: jnp.stack(xs),
                                             *stacked))
        return tuple(per_position)

    nd = cfg.first_dense
    if nd:
        params["dense_blocks"] = stack(range(nd), dense=True)
    params["blocks"] = stack(range(nd, n))
    return params


def param_spec(cfg: ModelConfig):
    """Shape/dtype pytree of the params, without allocating (for dry-runs)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))


# --------------------------------------------------------------------------
# shared backbone
# --------------------------------------------------------------------------

def _embed(cfg: ModelConfig, params, batch):
    dt = L.cdtype(cfg)
    if cfg.frontend is not None:
        x = batch["embeds"].astype(dt)
    else:
        # gather, then cast: casting the table first copies all of it
        x = jnp.take(params["embed"]["w"], batch["tokens"], axis=0).astype(dt)
    return constrain(x, "batch", None, None)


def _unembed(cfg: ModelConfig, params, x):
    dt = L.cdtype(cfg)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x.astype(dt),
                            params["embed"]["w"].astype(dt))
    else:
        # the head may arrive still sealed (tile layout) on the serving path
        logits = L.dense(x.astype(dt), params["head"]["w"], "bsd,dv->bsv", dt)
    logits = constrain(logits.astype(jnp.float32), "batch", None, "vocab")
    return L.softcap(logits, cfg.logit_softcap)


def scan_layers(cfg: ModelConfig, params, body, carry, per_layer=None):
    """``lax.scan`` of ``body(carry, (layer params, per-layer slice))`` over
    the super-blocks; ``per_layer`` is a pytree stacked over all of them (a
    cache, the paged pools) or None. A model with leading dense layers runs
    their stack first, then the main one; each scan indexes its layers'
    slices out of ``per_layer`` in place, and the per-layer outputs are
    joined in layer order."""
    if not cfg.first_dense:
        return lax.scan(body, carry, (params["blocks"], per_layer))
    at = lambda i: jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), per_layer)
    ys = []
    for stack, layers in ((params["dense_blocks"], (0, cfg.first_dense)),
                          (params["blocks"],
                           (cfg.first_dense, cfg.n_superblocks()))):
        carry, y = lax.scan(lambda c, xs: body(c, (xs[0], at(xs[1]))),
                            carry, (stack, jnp.arange(*layers)))
        ys.append(y)
    return carry, jax.tree.map(lambda *a: jnp.concatenate(a), *ys)


def _run_layers(cfg: ModelConfig, params, x, positions, mode, cache, remat: str):
    """Scan the super-block stack. Returns (x, new_cache, aux)."""

    def body(carry, xs):
        h, aux = carry
        p_slices, c_slices = xs
        if c_slices is None:
            c_slices = tuple(None for _ in cfg.pattern)
        new_caches = []
        for j, kind in enumerate(cfg.pattern):
            cj = c_slices[j] if c_slices[j] is not None else None
            h, nc, a = B.block_apply(cfg, kind, p_slices[j], h, positions, mode, cj)
            aux = aux + a
            new_caches.append(nc)
        ys = tuple(new_caches) if mode in ("prefill", "decode") else 0
        return (h, aux), ys

    if remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    elif remat == "save_carries":
        body = jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names())

    # prefill consumes an (empty) cache pytree to define slot shapes
    per_layer = cache if mode in ("decode", "prefill") else None
    (x, aux), ys = scan_layers(cfg, params, body,
                               (x, jnp.zeros((), jnp.float32)), per_layer)
    new_cache = ys if mode in ("prefill", "decode") else None
    return x, new_cache, aux


# --------------------------------------------------------------------------
# train / prefill / decode entry points
# --------------------------------------------------------------------------

def forward(cfg: ModelConfig, params, batch, *, remat: str = "none"):
    """Training/eval forward. batch: {tokens|embeds, targets}. Returns
    (loss, metrics) with CE loss in f32."""
    x = _embed(cfg, params, batch)
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)
    x, _, aux = _run_layers(cfg, params, x, positions, "train", None, remat)
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = _unembed(cfg, params, x)
    targets = batch["targets"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux,
                  "accuracy": jnp.mean(jnp.argmax(logits, -1) == targets)}


def prefill_hidden(cfg: ModelConfig, params, batch, cache_len: int):
    """Prompt pass up to the final norm: (normed hidden (B, S, D), cache).

    Shared by ``prefill`` (which unembeds the last position) and the paged
    serving path (which unembeds a per-request last position and rewrites
    the contiguous cache into sealed pool blocks).
    """
    x = _embed(cfg, params, batch)
    b = x.shape[0]
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    cache0 = model_cache_init(cfg, b, cache_len)
    x, cache, _ = _run_layers(cfg, params, x, positions, "prefill", cache0, "none")
    return L.apply_norm(cfg, params["final_norm"], x), cache


def prefill(cfg: ModelConfig, params, batch, cache_len: int):
    """Run the prompt, return (logits_last, cache). batch: {tokens|embeds}."""
    x, cache = prefill_hidden(cfg, params, batch, cache_len)
    logits = _unembed(cfg, params, x[:, -1:])
    return logits[:, 0], cache


def apply_cache_updates(cfg: ModelConfig, cache, updates, pos):
    """Merge the scan's per-layer decode update records into the cache.

    Attention layers emit {k_new, v_new} (written at slot = pos %
    cache_len — ring semantics for sliding windows); recurrent/SSD layers
    emit their full (tiny) new state.
    """
    new = []
    for j, kind in enumerate(cfg.pattern):
        cj, uj = cache[j], updates[j]
        if kind in ("attn", "local_attn") and cfg.mla is not None:
            slot = pos % cj["c"].shape[2]
            new.append({"c": cj["c"].at[:, :, slot].set(uj["c_new"][:, :, 0]),
                        "pos": cj["pos"].at[:, slot].set(pos)})
        elif kind in ("attn", "local_attn"):
            cache_len = cj["k"].shape[2]
            slot = pos % cache_len
            new.append({
                "k": cj["k"].at[:, :, slot].set(uj["k_new"][:, :, 0]),
                "v": cj["v"].at[:, :, slot].set(uj["v_new"][:, :, 0]),
                "pos": cj["pos"].at[:, slot].set(pos),
            })
        else:
            new.append(uj)
    return tuple(new)


def decode_step(cfg: ModelConfig, params, cache, batch, pos):
    """One serve step: new token(s) at position ``pos`` against the cache.

    batch: {tokens: (B,1)} or {embeds: (B,1,D)}; pos: scalar int32.
    Returns (logits (B, V), new_cache, next_token (B,)).
    """
    x = _embed(cfg, params, batch)
    positions = jnp.full((1,), pos, jnp.int32)
    x, updates, _ = _run_layers(cfg, params, x, positions, "decode", cache, "none")
    cache = apply_cache_updates(cfg, cache, updates, pos)
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = _unembed(cfg, params, x)[:, 0]
    next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return logits, cache, next_token
