"""Train / prefill step factories (the functions pjit lowers)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig, TrainConfig
from repro.models import transformer as T
from repro.optim import adamw, schedule


def make_loss_fn(cfg: ModelConfig, remat: str):
    def loss_fn(params, batch):
        loss, metrics = T.forward(cfg, params, batch, remat=remat)
        return loss, metrics
    return loss_fn


def make_train_step(cfg: ModelConfig, tc: TrainConfig):
    """Returns train_step(params, opt_state, batch) -> (params', opt', metrics).

    Gradient accumulation: the global batch is split into ``tc.microbatches``
    micro-batches scanned sequentially; grads are averaged in f32. This is
    also the compute/communication overlap lever — the per-microbatch
    reduce-scatters pipeline against the next microbatch's compute.
    """
    loss_fn = make_loss_fn(cfg, tc.remat)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        n = tc.microbatches

        if n > 1:
            def split(x):
                # microbatch i takes rows i, i+n, ...: the (data-sharded)
                # batch axis stays the minor one and the scanned axis is
                # unsharded, which lax.scan requires of its xs
                b = x.shape[0]
                return jnp.swapaxes(
                    x.reshape((b // n, n) + x.shape[1:]), 0, 1)
            micro = jax.tree.map(split, batch)

            def body(acc, mb):
                (loss, metrics), grads = grad_fn(params, mb)
                acc_g, acc_l = acc
                acc_g = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / n,
                                     acc_g, grads)
                return (acc_g, acc_l + loss / n), metrics

            zero = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
            (grads, loss), metrics_stack = lax.scan(body, (zero, 0.0), micro)
            metrics = jax.tree.map(lambda m: m[-1], metrics_stack)
        else:
            (loss, metrics), grads = grad_fn(params, batch)

        lr = schedule.lr_at(opt_state["step"], tc)
        params, opt_state, gnorm = adamw.update(params, opt_state, grads, lr, tc)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, cache_len: int, batch_chunks: int = 1):
    """Prefill, optionally processing the request batch in ``batch_chunks``
    sequential chunks (lax.map) — bounds the 32k-token transient
    activations (MoE dispatch buffers at 1M tokens blew 26 GB/device on the
    dbrx dry-run at chunks=1)."""
    def prefill_step(params, batch):
        if batch_chunks <= 1:
            return T.prefill(cfg, params, batch, cache_len)
        b = jax.tree.leaves(batch)[0].shape[0]
        assert b % batch_chunks == 0, (b, batch_chunks)
        bc = b // batch_chunks
        split = jax.tree.map(
            lambda x: x.reshape((batch_chunks, bc) + x.shape[1:]), batch)
        logits, caches = lax.map(
            lambda mb: T.prefill(cfg, params, mb, cache_len), split)
        logits = logits.reshape((b,) + logits.shape[2:])

        def merge(path, leaf):
            name = str(getattr(path[-1], "key", path[-1]))
            if name == "pos":            # identical across chunks
                return leaf[0]
            # (nc, n_super, bc, ...) -> (n_super, nc*bc, ...)
            out = jnp.moveaxis(leaf, 0, 1)
            return out.reshape((out.shape[0], b) + out.shape[3:])

        flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
        cache = jax.tree_util.tree_unflatten(
            treedef, [merge(kp, lf) for kp, lf in flat])
        return logits, cache
    return prefill_step
