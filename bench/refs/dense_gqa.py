"""Plain reference of a dense decoder with grouped-query attention.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, with no
kernel, cache, batching or sealing: pre-norm RMSNorm blocks, rotary
embeddings on the two halves of each head, causal softmax attention where
query head h reads key/value head h // (heads / kv_heads), a SiLU-gated MLP,
a final RMSNorm and an output projection (the embedding's transpose where
the embeddings are tied). It imports nothing of the program.

Weights are made here from the seed, one layer at a time inside the layer
scan, by the same random draws as the program's initialisation (normal,
scaled by fan-in; norm scales 1), so no weight the program made is used and
the whole model is never held at once.

``quant="fp8"`` is the control: the reference with the operands of every
projection rounded to float8 (e4m3, a scale per output channel of the
weights and per row of the activations) and products accumulated in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, x, w, quant, w_in_axes):
    """``einsum(eq, x, w)``; under the control both operands go through
    float8 first (weights scaled per output channel, activations per row)."""
    if quant == "fp8":
        w = _fp8(w, w_in_axes)
        x = _fp8(x, -1)
    return jnp.einsum(eq, x, w, precision=HI)


def _rms(x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[..., None].astype(jnp.float32) * freqs          # (B, S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_weights(c, key):
    d, h, kv, hd, f = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    _, k2, k3 = jax.random.split(key, 3)
    kq, kk, kvv, ko = jax.random.split(k2, 4)
    ki, kg, km = jax.random.split(k3, 3)
    n = jax.random.normal
    return {"wq": n(kq, (d, h, hd)) * d ** -0.5,
            "wk": n(kk, (d, kv, hd)) * d ** -0.5,
            "wv": n(kvv, (d, kv, hd)) * d ** -0.5,
            "wo": n(ko, (h, hd, d)) * (h * hd) ** -0.5,
            "wi": n(ki, (d, f)) * d ** -0.5,
            "wg": n(kg, (d, f)) * d ** -0.5,
            "wm": n(km, (f, d)) * f ** -0.5}


def _split_key(key):
    ke, kh, _, kb = jax.random.split(key, 4)
    return ke, kh, kb


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def logits_at(spec, key, tokens, positions, quant=None):
    """Logits (B, P, V) f32 at ``positions`` (B, P) of the right-padded
    sequences ``tokens`` (B, S): the prediction of the token after each
    such position. ``spec`` is the configuration as a sorted tuple of
    (key, value) pairs, ``key`` the model's random key."""
    c = dict(spec)
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    ke, kh, kb = _split_key(key)
    embed = jax.random.normal(ke, (c["vocab_size"], d)) * d ** -0.5
    if quant == "fp8":
        embed = _fp8(embed, -1)
    b, s = tokens.shape
    x = embed[tokens]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    causal = jnp.tril(jnp.ones((s, s), bool))
    g = h // kv

    def layer(x, i):
        w = _layer_weights(c, jax.random.fold_in(kb, i * 131))
        y = _rms(x, eps)
        q = _rope(_mm("bsd,dhk->bshk", y, w["wq"], quant, 0), pos, theta)
        k = _rope(_mm("bsd,dhk->bshk", y, w["wk"], quant, 0), pos, theta)
        v = _mm("bsd,dhk->bshk", y, w["wv"], quant, 0)
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        sc = jnp.einsum("bshk,bthk->bhst", q, k, precision=HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhst,bthk->bshk", p, v, precision=HI)
        x = x + _mm("bshk,hkd->bsd", o, w["wo"], quant, (0, 1))
        y = _rms(x, eps)
        m = (jax.nn.silu(_mm("bsd,df->bsf", y, w["wg"], quant, 0))
             * _mm("bsd,df->bsf", y, w["wi"], quant, 0))
        return x + _mm("bsf,fd->bsd", m, w["wm"], quant, 0), None

    x, _ = lax.scan(layer, x, jnp.arange(c["num_hidden_layers"]))
    x = _rms(jnp.take_along_axis(x, positions[..., None], axis=1), eps)
    if c["tie_word_embeddings"]:
        return _mm("bpd,vd->bpv", x, embed, quant, 1)
    head = jax.random.normal(kh, (d, c["vocab_size"])) * d ** -0.5
    return _mm("bpd,dv->bpv", x, head, quant, 0)
