"""Core layer math: norms, RoPE, attention (GQA / sliding-window / softcap),
dense & MoE MLPs. Pure functions over param pytrees.

Conventions:
  * params are stored in float32, compute is bf16 (cfg.dtype) with f32
    softmax/norm accumulation;
  * activations: (batch, seq, d_model); heads kept as an explicit axis so
    sharding constraints never cross a reshape.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig
from repro.core.sealed_tensor import SealedTensor
from repro.sharding.api import constrain, logical_spec

# --------------------------------------------------------------------------
# misc
# --------------------------------------------------------------------------

def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense(x, w, eq: str, dt):
    """Weight contraction that accepts either a plain array (einsum) or a
    still-sealed ``SealedTensor`` (fused decrypt-in-matmul Pallas kernel).

    The sealed branch flattens x's trailing contraction axes to (M, K),
    runs ``x2d @ decrypt(w)`` with the decrypt fused into the matmul (the
    plaintext weight never materializes in HBM), and restores the einsum's
    output shape. Operands are rounded to ``dt`` inside the kernel so both
    branches share the model compute precision.
    """
    if not isinstance(w, SealedTensor):
        return jnp.einsum(eq, x, w.astype(dt))
    kd = w.meta.k_ndim
    lead = x.shape[:x.ndim - kd]
    k = 1
    for d_ in x.shape[x.ndim - kd:]:
        k *= d_
    y = w.matmul(x.reshape(-1, k).astype(jnp.float32),
                 compute_dtype=str(jnp.dtype(dt)))
    return y.reshape(lead + w.out_shape).astype(dt)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True),
            "relu": jax.nn.relu}[name]


def softcap(x, cap: float):
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


@jax.custom_vjp
def pin(x):
    """``optimization_barrier`` with a gradient rule (the primitive has no
    differentiation rule, which broke MoE training). The cotangent is
    barriered too so the bwd pass keeps the same dtype pinning."""
    return lax.optimization_barrier(x)


def _pin_fwd(x):
    return lax.optimization_barrier(x), None


def _pin_bwd(_, g):
    return (lax.optimization_barrier(g),)


pin.defvjp(_pin_fwd, _pin_bwd)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rmsnorm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg: ModelConfig, p, x):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


def init_norm(cfg: ModelConfig, key):
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                "bias": jnp.zeros((cfg.d_model,), jnp.float32)}
    return {"scale": jnp.zeros((cfg.d_model,), jnp.float32)}


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                      # (half,)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., seq, half)
    cos = jnp.cos(ang)[..., None, :]                            # (..., seq, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x32_1, x32_2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x32_1 * cos - x32_2 * sin, x32_2 * cos + x32_1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, hq, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    he = cfg.heads_eff
    s = d ** -0.5
    wq = jax.random.normal(kq, (d, he, dh)) * s
    wo = jax.random.normal(ko, (he, dh, d)) * (hq * dh) ** -0.5
    if he > hq:
        # pad WITHIN each GQA group (zero heads at each group's tail) so
        # q-head -> kv-head assignment is unchanged; zero wq/wo rows make
        # the padded heads exact no-ops.
        g_old, g_new = hq // hkv, he // hkv
        assert he % hkv == 0
        mask = (jnp.arange(g_new) < g_old)            # (g_new,)
        mask_h = jnp.tile(mask, hkv)                  # (he,) group-major
        wq = jnp.where(mask_h[None, :, None], wq, 0.0)
        wo = jnp.where(mask_h[:, None, None], wo, 0.0)
    return {
        "wq": wq.astype(jnp.float32),
        "wk": (jax.random.normal(kk, (d, hkv, dh)) * s).astype(jnp.float32),
        "wv": (jax.random.normal(kv, (d, hkv, dh)) * s).astype(jnp.float32),
        "wo": wo.astype(jnp.float32),
    }


def _attn_mask(q_pos, k_pos, window: int):
    """(..., q, k) boolean mask: causal, optionally sliding-window.

    Accepts 1-D (q,)/(k,) positions (shared across the batch) or batched
    (b, q)/(b, k) positions (paged decode, where every slot sits at its own
    sequence offset); leading axes broadcast.
    """
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window:
        m &= (q_pos[..., :, None] - k_pos[..., None, :]) < window
    return m


def _sdpa(q, k, v, mask, attn_softcap: float, scale: float,
          q_chunk: int = 0, constrain_heads: bool = True):
    """q:(b,s,hq,dh) k,v:(b,t,hkv,dh) mask:(s,t) or (b,s,t) -> (b,s,hq,dh).

    GQA is realized by REPEATING k/v to the full head count instead of
    reshaping q into (kv, group) — a (48 -> 8x6) reshape cannot be
    propagated by GSPMD across a 16-way head sharding, which replicated
    the S x S score tensor per device (24 GB/device on the 33B dry-run).
    The repeat keeps the head axis intact and the scores sharded.

    q_chunk: process queries in checkpointed chunks of this size — bounds
    the live score buffer to (b, h, q_chunk, t) for archs whose head count
    cannot shard (e.g. 56 heads on a 16-way axis).
    """
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        if constrain_heads:
            # self-attention path: shard the repeated heads over `model`.
            # Decode must NOT do this — the cache arrives seq-sharded
            # (context-parallel) and re-sharding seq->heads makes GSPMD
            # replicate the whole cache per step (45 GB/device collective
            # on the granite decode_32k dry-run).
            k = constrain(k, "batch", None, "heads", "head_dim")
            v = constrain(v, "batch", None, "heads", "head_dim")
    if mask.ndim == 2:
        mask = mask[None]

    def attend(qc, mc):
        scores = jnp.einsum("bshd,bthd->bhst", qc, k,
                            preferred_element_type=jnp.float32) * scale
        if constrain_heads:
            scores = constrain(scores, "batch", "heads", None, None)
        else:
            # context-parallel decode: keep scores sharded along the cache
            # seq axis; softmax reduces via tiny per-(b,h) all-reduces and
            # the value contraction partial-sums — instead of all-gathering
            # the whole KV cache per layer (1.09 GB/layer on granite
            # decode_32k before this constraint).
            scores = constrain(scores, "batch", None, None, "cache_seq")
        scores = softcap(scores, attn_softcap)
        scores = jnp.where(mc[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(qc.dtype)
        out = jnp.einsum("bhst,bthd->bshd", probs, v)
        return out

    if q_chunk and s > q_chunk and s % q_chunk == 0:
        nq = s // q_chunk
        qs = q.reshape(b, nq, q_chunk, hq, dh)
        ms = mask.reshape(mask.shape[0], nq, q_chunk, mask.shape[-1])

        @jax.checkpoint
        def body(i):
            return attend(qs[:, i], ms[:, i])

        outs = lax.map(body, jnp.arange(nq))       # (nq, b, qc, h, d)
        return jnp.moveaxis(outs, 0, 1).reshape(b, s, hq, dh)
    return attend(q, mask)


def blockwise_attention(q, k, v, q_positions, k_positions, window: int,
                        attn_softcap: float, scale: float,
                        q_block: int = 512, kv_block: int = 1024):
    """FlashAttention-style online-softmax attention (forward only).

    Scans q blocks; per q block runs a fori_loop over only the kv blocks that
    can be live under the causal(+window) mask, so HLO FLOPs ~ the true
    masked work instead of the dense s*t rectangle. Memory is O(blocks),
    which is what lets prefill_32k compile inside a v5e HBM budget.
    """
    b, s, hq, dh = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    nq = -(-s // q_block)
    nk = -(-t // kv_block)
    qpad, tpad = nq * q_block - s, nk * kv_block - t
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, qpad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, qpad), constant_values=-1)
    if tpad:
        k = jnp.pad(k, ((0, 0), (0, tpad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, tpad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, tpad), constant_values=2**30)

    q = q.reshape(b, nq, q_block, hkv, g, dh)
    qpos = q_positions.reshape(nq, q_block)

    def one_q_block(qi):
        qb = q[:, qi]                      # (b, Qb, hkv, g, dh)
        qp = qpos[qi]                      # (Qb,)
        # kv block j is live iff some k_pos <= max q_pos and (window)
        hi = jnp.max(qp)
        lo = jnp.where(window > 0, jnp.maximum(jnp.min(qp) - window + 1, 0), 0)
        j_lo = lo // kv_block
        j_hi = jnp.minimum(hi // kv_block + 1, nk)

        def body(j, carry):
            acc, m_run, d_run = carry
            kb = lax.dynamic_slice_in_dim(k, j * kv_block, kv_block, axis=1)
            vb = lax.dynamic_slice_in_dim(v, j * kv_block, kv_block, axis=1)
            kp = lax.dynamic_slice_in_dim(k_positions, j * kv_block, kv_block)
            sc = jnp.einsum("bqkgd,btkd->bkgqt", qb, kb,
                            preferred_element_type=jnp.float32) * scale
            sc = softcap(sc, attn_softcap)
            msk = _attn_mask(qp, kp, window)
            sc = jnp.where(msk[None, None, None], sc, -1e30)
            m_new = jnp.maximum(m_run, jnp.max(sc, axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(sc - m_new[..., None])
            d_new = d_run * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqt,btkd->bkgqd", p.astype(vb.dtype), vb)
            acc = acc * alpha[..., None] + pv.astype(jnp.float32)
            return acc, m_new, d_new

        acc0 = jnp.zeros((b, hkv, g, q_block, dh), jnp.float32)
        m0 = jnp.full((b, hkv, g, q_block), -jnp.inf, jnp.float32)
        d0 = jnp.zeros((b, hkv, g, q_block), jnp.float32)
        acc, m_run, d_run = lax.fori_loop(j_lo, j_hi, body, (acc0, m0, d0))
        out = acc / jnp.maximum(d_run, 1e-30)[..., None]
        return out.astype(q.dtype)       # (b, hkv, g, Qb, dh)

    outs = lax.map(one_q_block, jnp.arange(nq))        # (nq, b, hkv, g, Qb, dh)
    outs = jnp.moveaxis(outs, 0, 1)                    # (b, nq, hkv, g, Qb, dh)
    outs = jnp.transpose(outs, (0, 1, 4, 2, 3, 5)).reshape(
        b, nq * q_block, hq, dh)
    return outs[:, :s]


def attention_apply(cfg: ModelConfig, p, x, positions, *, window: int,
                    impl: str = "naive", kv_override=None, attend=None):
    """Self-attention over x; returns (out, (k, v)) so callers can build caches.

    kv_override: (k, v, k_positions) — used at decode time to attend into a
    cache instead of self-computed kv. attend: a function of the roped
    queries that returns their attention output and a second value, which
    is returned in place of (k, v) — the paged serving path's read of the
    cache pool, with its integrity verdict.
    """
    dt = cdtype(cfg)
    xb = x.astype(dt)
    q = dense(xb, p["wq"], "bsd,dhk->bshk", dt)
    q = constrain(q, "batch", None, "heads", "head_dim")
    scale = cfg.head_dim ** -0.5
    if attend is not None:
        out, kv = attend(apply_rope(q, positions, cfg.rope_theta))
    elif kv_override is None:
        k = dense(xb, p["wk"], "bsd,dhk->bshk", dt)
        v = dense(xb, p["wv"], "bsd,dhk->bshk", dt)
        k = constrain(k, "batch", None, "kv_heads", "kv_head_dim")
        v = constrain(v, "batch", None, "kv_heads", "kv_head_dim")
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        k_positions = positions
        if impl == "blockwise":
            with jax.named_scope("attention"):
                out = blockwise_attention(q, k, v, positions, k_positions,
                                          window, cfg.attn_softcap, scale)
        else:
            mask = _attn_mask(positions, k_positions, window)
            # bound score memory when the head axis cannot shard
            hs = logical_spec("heads")
            heads_unsharded = hs is None or hs[0] is None
            qc = 512 if (heads_unsharded and x.shape[1] >= 4096) else 0
            with jax.named_scope("attention"):
                out = _sdpa(q, k, v, mask, cfg.attn_softcap, scale,
                            q_chunk=qc)
        kv = (k, v)
    else:
        k, v, k_positions = kv_override
        q = apply_rope(q, positions, cfg.rope_theta)
        mask = _attn_mask(positions, k_positions, window)
        with jax.named_scope("attention"):
            out = _sdpa(q, k, v, mask, cfg.attn_softcap, scale,
                        constrain_heads=False)
        kv = (k, v)
    y = dense(out, p["wo"], "bshk,hkd->bsd", dt)
    y = constrain(y, "batch", None, None)
    return y, kv


# --------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2/V3, no query LoRA)
# --------------------------------------------------------------------------

def init_mla(cfg: ModelConfig, key):
    """MLA projections. ``wkv_a`` (d, rank + rope) and ``wkv_b`` (rank,
    heads, nope + v) are drawn whole, as published, and kept in parts: the
    latent's ``wkv_a`` (d, rank) and the rotary key's ``wk_rope`` (d, rope),
    whose tiles each fit one integrity tag, and ``wk_b`` (heads, nope, rank)
    and ``wv_b`` (rank, heads, v), the layouts the absorbed attention
    consumes."""
    a, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    kq, ka, kb, ko = jax.random.split(key, 4)
    wkv_a = jax.random.normal(ka, (d, a.latent)) * d ** -0.5
    wkv_b = (jax.random.normal(kb, (a.kv_lora_rank, h, a.nope_dim + a.v_dim))
             * a.kv_lora_rank ** -0.5)
    return {
        "wq": (jax.random.normal(kq, (d, h, cfg.head_dim)) * d ** -0.5
               ).astype(jnp.float32),
        "wkv_a": wkv_a[:, :a.kv_lora_rank].astype(jnp.float32),
        "wk_rope": wkv_a[:, a.kv_lora_rank:].astype(jnp.float32),
        "kv_norm": {"scale": jnp.zeros((a.kv_lora_rank,), jnp.float32)},
        "wk_b": jnp.transpose(wkv_b[..., :a.nope_dim], (1, 2, 0)
                              ).astype(jnp.float32),
        "wv_b": wkv_b[..., a.nope_dim:].astype(jnp.float32),
        "wo": (jax.random.normal(ko, (h, a.v_dim, d)) * (h * a.v_dim) ** -0.5
               ).astype(jnp.float32),
    }


def mla_latent(cfg: ModelConfig, p, x, positions):
    """The cache entry of each token: [RMSNorm(c_kv) ; rope(k_pe)],
    (b, s, kv_lora_rank + rope_dim) in the compute dtype."""
    dt = cdtype(cfg)
    xb = x.astype(dt)
    c = rmsnorm(dense(xb, p["wkv_a"], "bsd,dc->bsc", dt),
                p["kv_norm"]["scale"])
    k_pe = apply_rope(dense(xb, p["wk_rope"], "bsd,dr->bsr", dt)[..., None, :],
                      positions, cfg.rope_theta)[..., 0, :]
    return jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1)


def _per_head_in(x, w, dt):
    """out[..., h, c] = sum_n x[..., h, n] w[h, n, c]. A sealed ``w`` is one
    (heads * n, c) matmul: each head's row reads a block-diagonal copy of
    its input, zero outside its own head's rows."""
    if not isinstance(w, SealedTensor):
        return jnp.einsum("bshn,hnc->bshc", x, w.astype(dt))
    h = x.shape[-2]
    eye = jnp.eye(h, dtype=x.dtype)
    xe = x[..., None, :, :] * eye[:, :, None]            # (b, s, g, h, n)
    return dense(xe, w, "bsghn,hnc->bsgc", dt)


def _per_head_out(x, w, dt):
    """out[..., h, v] = sum_c x[..., h, c] w[c, h, v]. A sealed ``w`` is one
    (c, heads * v) matmul, of which each head keeps its own columns."""
    if not isinstance(w, SealedTensor):
        return jnp.einsum("bshc,chv->bshv", x, w.astype(dt))
    y = dense(x, w, "bshc,cgv->bshgv", dt)               # (b, s, h, h, v)
    return jnp.moveaxis(jnp.diagonal(y, axis1=2, axis2=3), -1, 2)


def mla_apply(cfg: ModelConfig, p, x, positions, latent, k_positions):
    """Absorbed MLA over ``latent`` (b, t, rank + rope), the entries of the
    keys at ``k_positions``: query head h reads [W_UK,h^T q_nope,h ;
    rope(q_pe,h)] against every entry, one key "head" shared by all heads,
    and its output is W_UV,h applied to the probability-weighted c_kv.
    Softmax scale (nope + rope)^-1/2. Returns (b, s, d)."""
    a, dt = cfg.mla, cdtype(cfg)
    q = dense(x.astype(dt), p["wq"], "bsd,dhk->bshk", dt)
    q_pe = apply_rope(q[..., a.nope_dim:], positions, cfg.rope_theta)
    with jax.named_scope("mla_absorb"):
        q_lat = _per_head_in(q[..., :a.nope_dim], p["wk_b"], dt)
    qf = jnp.concatenate([q_lat, q_pe.astype(dt)], axis=-1)
    mask = _attn_mask(positions, k_positions, 0)
    if mask.ndim == 2:
        mask = mask[None]
    lat = latent.astype(dt)
    with jax.named_scope("attention"):
        scores = jnp.einsum("bshc,btc->bhst", qf, lat,
                            preferred_element_type=jnp.float32)
        scores = jnp.where(mask[:, None], scores * cfg.head_dim ** -0.5,
                           -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        o_lat = jnp.einsum("bhst,btc->bshc", probs, lat[..., :a.kv_lora_rank])
    with jax.named_scope("mla_absorb"):
        o = _per_head_out(o_lat, p["wv_b"], dt)
    return dense(o, p["wo"], "bshk,hkd->bsd", dt)


def project_kv(cfg: ModelConfig, p, x, positions):
    """Just the k,v projections (+rope on k) — used when writing decode caches."""
    dt = cdtype(cfg)
    xb = x.astype(dt)
    k = dense(xb, p["wk"], "bsd,dhk->bshk", dt)
    v = dense(xb, p["wv"], "bsd,dhk->bshk", dt)
    k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


# --------------------------------------------------------------------------
# MLP (dense + MoE)
# --------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key):
    d, f = cfg.d_model, cfg.d_ff
    ki, kg, ko = jax.random.split(key, 3)
    s_in, s_out = d ** -0.5, f ** -0.5
    if cfg.moe is not None:
        e = cfg.moe.num_experts
        kr = jax.random.fold_in(key, 7)
        return {
            "router": (jax.random.normal(kr, (d, e)) * s_in).astype(jnp.float32),
            "wi": (jax.random.normal(ki, (e, d, f)) * s_in).astype(jnp.float32),
            "wg": (jax.random.normal(kg, (e, d, f)) * s_in).astype(jnp.float32),
            "wo": (jax.random.normal(ko, (e, f, d)) * s_out).astype(jnp.float32),
        }
    return {
        "wi": (jax.random.normal(ki, (d, f)) * s_in).astype(jnp.float32),
        "wg": (jax.random.normal(kg, (d, f)) * s_in).astype(jnp.float32),
        "wo": (jax.random.normal(ko, (f, d)) * s_out).astype(jnp.float32),
    }


def mlp_apply(cfg: ModelConfig, p, x):
    dt = cdtype(cfg)
    xb = x.astype(dt)
    a = act_fn(cfg.act)
    h = a(dense(xb, p["wg"], "bsd,df->bsf", dt)) * \
        dense(xb, p["wi"], "bsd,df->bsf", dt)
    h = constrain(h, "batch", None, "ff")
    out = dense(h, p["wo"], "bsf,fd->bsd", dt)
    return constrain(out, "batch", None, None)


def moe_router(cfg: ModelConfig, p, x2d):
    """Router: returns (gate_vals (t,k), gate_idx (t,k), aux_loss)."""
    moe = cfg.moe
    logits = jnp.einsum("td,de->te", x2d, p["router"].astype(x2d.dtype)
                        ).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = lax.top_k(probs, moe.top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(
        gate_idx, moe.num_experts, dtype=jnp.float32), axis=1), axis=0)
    aux = moe.aux_loss_weight * moe.num_experts * jnp.sum(me * ce)
    return gate_vals, gate_idx, aux


def moe_apply_dense(cfg: ModelConfig, p, x):
    """Dropless MoE: dense einsum over all experts, gated top-k combine.

    Exact (no capacity drops); FLOPs inflate by E/k, so this is the decode
    path (tiny token counts) and the testing oracle, not the training path.
    """
    moe = cfg.moe
    dt = cdtype(cfg)
    b, s, d = x.shape
    t = b * s
    xb = x.reshape(t, d).astype(dt)
    gate_vals, gate_idx, aux = moe_router(cfg, p, xb)
    gates = jnp.zeros((t, moe.num_experts), jnp.float32)
    gates = gates.at[jnp.arange(t)[:, None], gate_idx].set(gate_vals)
    a = act_fn(cfg.act)
    h = a(jnp.einsum("td,edf->tef", xb, p["wg"].astype(dt))) * \
        jnp.einsum("td,edf->tef", xb, p["wi"].astype(dt))
    eout = jnp.einsum("tef,efd->ted", h, p["wo"].astype(dt))
    out = jnp.einsum("ted,te->td", eout, gates.astype(dt))
    return out.reshape(b, s, d), aux


MOE_TOKEN_CHUNK = 65_536


def moe_apply(cfg: ModelConfig, p, x, *, capacity_factor: float | None = None):
    """Capacity-based MoE with token chunking: dispatches of more than
    ``MOE_TOKEN_CHUNK`` tokens are processed in sequential chunks (each with
    its own capacity buffer) — bounds the (t*k, d) staging tensors and the
    scatter's sort scratch at 32k-prefill scale."""
    b, s, d = x.shape
    t = b * s
    nc = t // MOE_TOKEN_CHUNK if t > MOE_TOKEN_CHUNK else 1
    # chunk along SEQ (batch dim kept intact so its `data` sharding
    # survives the reshape; flattening (b, s) replicated the staging)
    if nc <= 1 or t % MOE_TOKEN_CHUNK or s % nc:
        return _moe_apply_block(cfg, p, x, capacity_factor=capacity_factor)
    xc = jnp.moveaxis(x.reshape(b, nc, s // nc, d), 1, 0)   # (nc, b, sc, d)

    def body(chunk):
        return _moe_apply_block(cfg, p, chunk, capacity_factor=capacity_factor)

    outs, auxs = jax.lax.map(body, xc)                      # (nc, b, sc, d)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, d)
    return out, jnp.mean(auxs)


def _moe_apply_block(cfg: ModelConfig, p, x, *, capacity_factor: float | None = None):
    """Capacity-based top-k MoE (GShard-style dispatch, EP-shardable).

    Tokens are routed to their top-k experts; each expert processes at most
    C = ceil(T * k / E * capacity_factor) tokens (overflow dropped, standard
    for capacity-based routing). Dispatch/combine are einsum-free scatters so
    the expert GEMMs are clean (E, C, d) x (E, d, f) contractions that shard
    over the `model` (expert) axis.

    Returns (out, aux_loss).
    """
    moe = cfg.moe
    dt = cdtype(cfg)
    b, s, d = x.shape
    t = b * s
    e, k = moe.num_experts, moe.top_k
    cf = capacity_factor if capacity_factor is not None else moe.capacity_factor
    cap = int(t * k / e * cf + 0.999)
    cap = max(min(cap, t), 1)

    xb = constrain(x.reshape(t, d).astype(dt), "moe_tokens", None)
    gate_vals, gate_idx, aux = moe_router(cfg, p, xb)   # (t, k) each

    # position of each (token, k) within its expert's capacity buffer
    flat_expert = gate_idx.reshape(-1)                                 # (t*k,)
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)           # (t*k, e)
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot)              # count before
    pos = jnp.take_along_axis(pos_in_expert, flat_expert[:, None], axis=1)[:, 0]
    keep = pos < cap
    slot = flat_expert * cap + jnp.where(keep, pos, 0)                 # (t*k,)

    # dispatch: (e*cap, d) buffer; the expert axis shards over `model`, so
    # this scatter lowers to the EP all-to-all. The (t*k, d) staging
    # tensors are pinned to the data axis — unconstrained they replicate
    # (3.2 GB/device on the dbrx dry-run).
    buf = jnp.zeros((e * cap, d), dt)
    tok_idx = jnp.repeat(jnp.arange(t), k)
    picked = constrain(xb[tok_idx], "moe_tokens", None)
    src = constrain(jnp.where(keep[:, None], picked, 0), "moe_tokens", None)
    # pin bf16 before the cross-axis scatter: XLA upcasts scatter-adds (and
    # the all-reduce realizing them across the data->expert axes) to f32,
    # doubling the dominant collective on the qwen3 train cell
    src = pin(src.astype(dt))
    buf = buf.at[slot].add(src)
    buf = constrain(buf.reshape(e, cap, d), "expert", None, None)

    a = act_fn(cfg.act)
    h = a(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(dt))) * \
        jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(dt))
    h = constrain(h, "expert", None, "moe_ff")
    eout = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt))
    eout = constrain(eout, "expert", None, None)
    # barrier: the f-contraction's cross-`data` psum runs in f32 on some
    # backends and convert-motion would propagate f32 through the combine
    # gather (2.15 GB/tensor at prefill_32k scale) — pin bf16 here.
    eout = pin(eout.astype(dt))
    eout = eout.reshape(e * cap, d)

    # combine
    gathered = constrain(eout[slot], "moe_tokens", None)               # (t*k, d)
    w = (gate_vals.reshape(-1) * keep).astype(dt)
    weighted = constrain(gathered * w[:, None], "moe_tokens", None)
    weighted = pin(weighted.astype(dt))
    out = jnp.zeros((t, d), dt).at[tok_idx].add(weighted)
    out = constrain(out, "moe_tokens", None)
    return out.reshape(b, s, d), aux


# --------------------------------------------------------------------------
# dropless MoE over the experts this chip holds (DeepSeek-V3 layer)
# --------------------------------------------------------------------------

def init_moe_held(cfg: ModelConfig, key):
    """Router over every routed expert with its correction bias, the held
    experts' weights, and the shared experts as one MLP. The bias is drawn
    from the key, so selection by score + bias differs from selection by
    score."""
    m, d = cfg.moe, cfg.d_model
    f = m.d_expert or cfg.d_ff
    ki, kg, ko = jax.random.split(key, 3)
    kr, kb, ks = (jax.random.fold_in(key, i) for i in (7, 8, 9))
    p = {
        "router": (jax.random.normal(kr, (d, m.num_experts)) * d ** -0.5
                   ).astype(jnp.float32),
        "score_bias": (jax.random.normal(kb, (m.num_experts,)) * 0.1
                       ).astype(jnp.float32),
        "experts": {
            "wi": (jax.random.normal(ki, (m.held, d, f)) * d ** -0.5
                   ).astype(jnp.float32),
            "wg": (jax.random.normal(kg, (m.held, d, f)) * d ** -0.5
                   ).astype(jnp.float32),
            "wo": (jax.random.normal(ko, (m.held, f, d)) * f ** -0.5
                   ).astype(jnp.float32),
        },
    }
    if m.d_shared:
        p["shared"] = init_mlp(cfg.with_(d_ff=m.d_shared, moe=None), ks)
    return p


def moe_route(cfg: ModelConfig, p, x2d):
    """DeepSeek-V3's top-k routing over every routed expert (noaux_tc with
    one group), in f32 as published implementations do: the choice is the
    top-k of sigmoid score + correction bias, and the weights are the
    chosen (unbiased) scores, normalised over the k and scaled by
    ``route_scale``. Returns (weights (t, k) f32, expert ids (t, k) i32)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(scores + p["score_bias"], m.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * m.route_scale, idx


def grouped_matmul(xs, w, dt):
    """(E, T, K) x (E, K, N) -> (E, T, N) in ``dt``; a sealed ``w`` goes
    still sealed into ``kernels.sealed_gmm``."""
    if isinstance(w, SealedTensor):
        return w.gmm(xs, compute_dtype=str(jnp.dtype(dt))).astype(dt)
    return jnp.einsum("etk,ekn->etn", xs, w.astype(dt))


def moe_held(cfg: ModelConfig, p, x, live=None, first: int = 0):
    """Dropless MoE layer at one chip's share: routes every token over all
    routed experts, computes the part of the result that the held experts
    [first, first + held) give, and adds the shared experts.

    The (token, expert) pairs that land on held experts are grouped into
    one slab of T rows per held expert (T = tokens in the call, the most
    one expert can receive, so no pair is ever dropped): slab row r of
    expert e holds the r-th token routed to e, in token order. Each token's
    output depends only on its own routes. Returns (out (b, s, d), routes
    (2,) u32: pairs of ``live`` tokens landing on held experts, and all
    their pairs)."""
    m, dt = cfg.moe, cdtype(cfg)
    b, s, d = x.shape
    t = b * s
    xb = x.reshape(t, d).astype(dt)
    ex, e_h = p["experts"], m.held
    with jax.named_scope("moe_route"):
        w, idx = moe_route(cfg, p, xb)
        loc = idx - first                                  # (t, k)
        held = (loc >= 0) & (loc < e_h)
        loc = jnp.where(held, loc, 0)
        onehot = (loc[..., None] == jnp.arange(e_h)) & held[..., None]
        pres = jnp.any(onehot, axis=1).astype(jnp.int32)   # (t, e_h)
        rank = jnp.cumsum(pres, axis=0) - pres             # tokens before
        slot = jnp.take_along_axis(rank, loc, axis=1)      # (t, k)
        tok = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[:, None],
                               loc.shape)
        src = jnp.full((e_h, t), t, jnp.int32).at[
            jnp.where(held, loc, e_h), slot].set(tok, mode="drop")
        lv = (jnp.ones((t,), bool) if live is None
              else jnp.broadcast_to(live, (b, s)).reshape(t))
        routes = jnp.stack([jnp.sum(held & lv[:, None]),
                            jnp.sum(lv) * m.top_k]).astype(jnp.uint32)
    with jax.named_scope("moe_experts"):
        xs = jnp.concatenate([xb, jnp.zeros((1, d), dt)])[src]  # (e_h, t, d)
        a = act_fn(cfg.act)
        h = (a(grouped_matmul(xs, ex["wg"], dt))
             * grouped_matmul(xs, ex["wi"], dt))
        y = grouped_matmul(h, ex["wo"], dt).reshape(e_h * t, d)
        yr = y[loc * t + slot]                             # (t, k, d)
        gate = jnp.where(held, w, 0.0)
        out = jnp.einsum("tkd,tk->td", yr.astype(jnp.float32), gate)
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            out = out + mlp_apply(cfg, p["shared"], xb[None]
                                  )[0].astype(jnp.float32)
    return out.astype(dt).reshape(b, s, d), routes
