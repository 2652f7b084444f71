"""Plain reference of a DeepSeek-V3 decoder (multi-head latent attention,
sigmoid-routed experts with shared experts, leading dense layers) at one
chip's share of its experts.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, with no
kernel, cache, batching or sealing, and the attention *un-absorbed*, as the
published modelling code computes it: per layer, a pre-norm RMSNorm; q =
x W_q split into a no-position part and a rotary part; the latent
[c_kv ; k_pe] = x W_kv_a, c_kv RMS-normalised; per-head keys [c_kv W_UK ;
rope(k_pe)] (one rotary key shared by all heads) and values c_kv W_UV;
causal softmax attention at scale (nope + rope)^-1/2; the output
projection. Then a pre-norm MLP: a SiLU-gated MLP in the leading dense
layers; in the others a router over every routed expert (sigmoid scores in
f32, the top-k of score + correction bias chosen, the chosen scores
normalised over the k and scaled by ``routed_scaling_factor``), of which
only the experts this chip holds (the first ``n_routed_experts`` of
``n_routed_experts_total``) are computed, plus the shared experts as one
MLP. A final RMSNorm and an untied output head. It imports nothing of the
program.

Departures from the published model, each listed in the configuration
file too: rotary pairs are the two halves of the rotary part (the
published code pairs interleaved columns: a permutation of the weights'
rotary columns); the program's norms use eps 1e-6 where this reference
uses the published 1e-5; weights are random from the seed (normal, scaled
by fan-in; norm scales 1; the correction bias normal at scale 0.1), not
the checkpoint; what the experts held elsewhere would add is left out, as
the program leaves it out.

Weights are made here from the seed, one layer at a time inside the layer
scans, by the same random draws as the program's initialisation, so no
weight the program made is used and the whole model is never held at once.

``quant="fp8"`` is the control: the reference with the operands of every
projection (the router's too) rounded to float8 (e4m3, a scale per output
channel of the weights and per row of the activations) and products
accumulated in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F8_MAX = 448.0
BIAS_SCALE = 0.1          # the correction bias's draw: normal x 0.1


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
    """x (B, S, H, D), pos (B, S): the two halves of D rotate as pairs."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[..., None].astype(jnp.float32) * freqs          # (B, S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _widths(c):
    return dict(d=c["hidden_size"], h=c["num_attention_heads"],
                rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                rope=c["qk_rope_head_dim"], v=c["v_head_dim"])


def _attn_weights(c, key):
    w = _widths(c)
    d, h = w["d"], w["h"]
    kq, ka, kb, ko = jax.random.split(key, 4)
    n = jax.random.normal
    return {"wq": n(kq, (d, h, w["nope"] + w["rope"])) * d ** -0.5,
            "wkv_a": n(ka, (d, w["rank"] + w["rope"])) * d ** -0.5,
            "wkv_b": n(kb, (w["rank"], h, w["nope"] + w["v"]))
            * w["rank"] ** -0.5,
            "wo": n(ko, (h, w["v"], d)) * (h * w["v"]) ** -0.5}


def _mlp_weights(d, f, key):
    ki, kg, km = jax.random.split(key, 3)
    n = jax.random.normal
    return {"wi": n(ki, (d, f)) * d ** -0.5, "wg": n(kg, (d, f)) * d ** -0.5,
            "wm": n(km, (f, d)) * f ** -0.5}


def _moe_weights(c, key):
    d, e_all = c["hidden_size"], c["n_routed_experts_total"]
    e_h, f = c["n_routed_experts"], c["moe_intermediate_size"]
    ki, kg, km = jax.random.split(key, 3)
    n = jax.random.normal
    return {"router": n(jax.random.fold_in(key, 7), (d, e_all)) * d ** -0.5,
            "bias": n(jax.random.fold_in(key, 8), (e_all,)) * BIAS_SCALE,
            "wi": n(ki, (e_h, d, f)) * d ** -0.5,
            "wg": n(kg, (e_h, d, f)) * d ** -0.5,
            "wm": n(km, (e_h, f, d)) * f ** -0.5,
            "shared": _mlp_weights(
                d, c["moe_intermediate_size"] * c["n_shared_experts"],
                jax.random.fold_in(key, 9))}


def _layer_keys(key):
    """(attention, MLP) keys of a layer's key, as the program splits it."""
    _, k2, k3 = jax.random.split(key, 3)
    return k2, k3


def _attention(c, w, y, pos, causal, quant):
    wd = _widths(c)
    nope, rank = wd["nope"], wd["rank"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    q = _mm("bsd,dhk->bshk", y, w["wq"], quant, 0)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)],
                        -1)
    kv = _mm("bsd,dc->bsc", y, w["wkv_a"], quant, 0)
    ckv = _rms(kv[..., :rank], eps)
    k_pe = _rope(kv[..., None, rank:], pos, theta)            # (B, S, 1, r)
    kvb = _mm("bsc,chk->bshk", ckv, w["wkv_b"], quant, 0)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:-1] + k_pe.shape[-1:])],
        -1)
    sc = jnp.einsum("bshk,bthk->bhst", q, k, precision=HI) \
        * (nope + wd["rope"]) ** -0.5
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhst,bthk->bshk", p, v, precision=HI)
    return _mm("bshk,hkd->bsd", o, w["wo"], quant, (0, 1))


def _mlp(w, y, quant):
    m = (jax.nn.silu(_mm("bsd,df->bsf", y, w["wg"], quant, 0))
         * _mm("bsd,df->bsf", y, w["wi"], quant, 0))
    return _mm("bsf,fd->bsd", m, w["wm"], quant, 0)


def _moe(c, w, y, quant):
    k = c["num_experts_per_tok"]
    e_h = c["n_routed_experts"]
    scores = jax.nn.sigmoid(_mm("bsd,de->bse", y, w["router"], quant, 0))
    _, idx = lax.top_k(scores + w["bias"], k)                  # (B, S, k)
    wt = jnp.take_along_axis(scores, idx, axis=-1)
    wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    wt = wt * c["routed_scaling_factor"]
    # weight of each held expert for each token (0 where not chosen)
    gate = jnp.sum(jnp.where(idx[..., None] == jnp.arange(e_h), wt[..., None],
                             0.0), axis=-2)                    # (B, S, e_h)
    h = (jax.nn.silu(_mm("bsd,edf->ebsf", y, w["wg"], quant, 1))
         * _mm("bsd,edf->ebsf", y, w["wi"], quant, 1))
    out = jnp.einsum("ebsd,bse->bsd",
                     _mm("ebsf,efd->ebsd", h, w["wm"], quant, 1), gate,
                     precision=HI)
    return out + _mlp(w["shared"], y, quant)


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def logits_at(spec, key, tokens, positions, quant=None):
    """Logits (B, P, V) f32 at ``positions`` (B, P) of the right-padded
    sequences ``tokens`` (B, S): the prediction of the token after each
    such position. ``spec`` is the configuration as a sorted tuple of
    (key, value) pairs, ``key`` the model's random key."""
    c = dict(spec)
    d, eps = c["hidden_size"], c["rms_norm_eps"]
    ke, kh, _, kb = jax.random.split(key, 4)
    embed = jax.random.normal(ke, (c["vocab_size"], d)) * d ** -0.5
    if quant == "fp8":
        embed = _fp8(embed, -1)
    b, s = tokens.shape
    x = embed[tokens]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    causal = jnp.tril(jnp.ones((s, s), bool))
    nd = c["first_k_dense_replace"]

    def layer(moe):
        def body(x, i):
            ka, km = _layer_keys(jax.random.fold_in(kb, i * 131))
            x = x + _attention(c, _attn_weights(c, ka), _rms(x, eps), pos,
                               causal, quant)
            y = _rms(x, eps)
            if moe:
                return x + _moe(c, _moe_weights(c, km), y, quant), None
            w = _mlp_weights(d, c["intermediate_size"], km)
            return x + _mlp(w, y, quant), None
        return body

    x, _ = lax.scan(layer(False), x, jnp.arange(nd))
    x, _ = lax.scan(layer(True), x, jnp.arange(nd, c["num_hidden_layers"]))
    x = _rms(jnp.take_along_axis(x, positions[..., None], axis=1), eps)
    head = jax.random.normal(kh, (d, c["vocab_size"])) * d ** -0.5
    return _mm("bpd,dv->bpv", x, head, quant, 0)
