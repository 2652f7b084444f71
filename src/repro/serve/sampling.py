"""Per-request token sampling: temperature / top-k / top-p, batched.

Every request carries its own PRNG stream: the engine derives a base key as
``fold_in(key(sample_seed), rid)`` and the n-th generated token of that
request uses ``fold_in(base_key, n)`` — fully deterministic given (seed,
rid, n), independent of slot placement and batch composition, so a replay
of the same trace is bit-reproducible.

All filters operate per row, so one batched call serves slots with mixed
settings (a greedy row next to a top-p row). ``temperature == 0`` selects
the exact argmax.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def request_key_data(sample_seed: int, rid: int):
    """(2,) u32 key data for a request's base PRNG key (host side)."""
    return jax.random.key_data(
        jax.random.fold_in(jax.random.key(sample_seed), rid))


def fold_token_keys(key_data, counts):
    """key_data: (B, 2) u32 per-request base keys; counts: (B,) int32 index
    of the token being sampled. Returns (B,) typed keys."""
    keys = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32))
    return jax.vmap(jax.random.fold_in)(keys, counts)


def sample_logits(logits, keys, temperature, top_k, top_p):
    """logits: (B, V) f32; keys: (B,) typed PRNG keys; temperature/top_k/
    top_p: (B,) per-row settings (top_k <= 0 means no top-k cut).

    Rows are sorted by logit descending, the top-k rank cut and the top-p
    nucleus cut (smallest prefix whose mass reaches top_p — an entry stays
    while the mass *before* it is < top_p, so the argmax always survives)
    are applied there, and the survivor set is sampled at ``logits /
    temperature``. Returns (B,) int32 tokens.

    An all-greedy batch (every temperature <= 0) short-circuits to a pure
    argmax under ``lax.cond`` — the vocab-wide argsort dominates the
    sampling cost, and greedy decode (the common serving default) never
    consults the sorted order.
    """
    with jax.named_scope("sampling"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def full(_):
            return _sample_full(logits, keys, temperature, top_k, top_p,
                                greedy)

        return jax.lax.cond(jnp.all(temperature <= 0),
                            lambda _: greedy, full, operand=None)


def _sample_full(logits, keys, temperature, top_k, top_p, greedy):
    v = logits.shape[1]
    t = jnp.maximum(temperature, 1e-6)[:, None]
    sort_idx = jnp.argsort(-logits, axis=-1)                    # descending
    sorted_scaled = jnp.take_along_axis(logits / t, sort_idx, axis=-1)
    probs = jax.nn.softmax(sorted_scaled, axis=-1)
    ranks = jnp.arange(v)[None, :]
    keep = ranks < jnp.where(top_k > 0, top_k, v)[:, None]
    cum = jnp.cumsum(probs, axis=-1)
    keep &= (cum - probs) < top_p[:, None]
    filt = jnp.where(keep, sorted_scaled, -jnp.inf)
    picked = jax.vmap(jax.random.categorical)(keys, filt)       # (B,) ranks
    sampled = jnp.take_along_axis(sort_idx, picked[:, None], axis=-1)[:, 0]
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)
