"""Residual blocks: attention (global/local), RG-LRU (Griffin), Mamba2-SSD.

Each block exposes:
  init_block(cfg, kind, key[, dense])                 -> params
  block_apply(cfg, kind, params, x, positions, mode, cache) -> (y, cache', aux)

mode: "train" | "prefill" | "decode" | "chunk". In decode mode x is
(B, 1, D) and the returned cache slice replaces the layer's cache. "chunk"
is the paged chunked-prefill mode: x is (B, C, D), the cache is the dense
paged view, and the chunk's fresh K/V are spliced in at their absolute
positions before attention.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models.cache import INVALID_POS
from repro.sharding.api import constrain

# --------------------------------------------------------------------------
# causal depthwise conv1d
# --------------------------------------------------------------------------

def causal_conv1d(x, w, b):
    """x: (B, S, C); w: (K, C); b: (C,). Depthwise causal conv."""
    k = w.shape[0]
    kern = w[:, None, :].astype(x.dtype)               # (K, 1, C)
    y = lax.conv_general_dilated(
        x, kern, window_strides=(1,), padding=[(k - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1])
    return y + b.astype(x.dtype)


def causal_conv1d_step(x_new, conv_cache, w, b):
    """x_new: (B, 1, C); conv_cache: (B, K-1, C). Returns (y (B,1,C), cache')."""
    full = jnp.concatenate([conv_cache.astype(x_new.dtype), x_new], axis=1)  # (B,K,C)
    y = jnp.einsum("bkc,kc->bc", full, w.astype(x_new.dtype)) + b.astype(x_new.dtype)
    return y[:, None], full[:, 1:]


# --------------------------------------------------------------------------
# RG-LRU recurrent block (Griffin / RecurrentGemma) [arXiv:2402.19427]
# --------------------------------------------------------------------------

_RGLRU_C = 8.0


def init_rglru(cfg: ModelConfig, key):
    d = cfg.d_model
    w = cfg.rglru_block_width or d
    ks = jax.random.split(key, 6)
    s_d, s_w = d ** -0.5, w ** -0.5
    return {
        "w_x": (jax.random.normal(ks[0], (d, w)) * s_d).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (d, w)) * s_d).astype(jnp.float32),
        "conv_w": (jax.random.normal(ks[2], (4, w)) * 0.1).astype(jnp.float32),
        "conv_b": jnp.zeros((w,), jnp.float32),
        "w_rg": (jax.random.normal(ks[3], (w, w)) * s_w).astype(jnp.float32),
        "b_rg": jnp.zeros((w,), jnp.float32),
        "w_ig": (jax.random.normal(ks[4], (w, w)) * s_w).astype(jnp.float32),
        "b_ig": jnp.zeros((w,), jnp.float32),
        # Lambda init so a^c in [0.9, 0.999] as in the paper
        "lam": jnp.log(jnp.expm1(
            jnp.linspace(0.9, 0.999, w) ** -(1 / _RGLRU_C) - 1 + 1e-8)).astype(jnp.float32),
        "w_out": (jax.random.normal(ks[5], (w, d)) * s_w).astype(jnp.float32),
    }


def _rglru_coeffs(p, xa):
    """Per-step recurrence coefficients. xa: (B,S,W) conv output."""
    dt = xa.dtype
    r = jax.nn.sigmoid(jnp.einsum("bsw,wv->bsv", xa, p["w_rg"].astype(dt))
                       + p["b_rg"].astype(dt)).astype(jnp.float32)
    i = jax.nn.sigmoid(jnp.einsum("bsw,wv->bsv", xa, p["w_ig"].astype(dt))
                       + p["b_ig"].astype(dt)).astype(jnp.float32)
    log_a = -_RGLRU_C * r * jax.nn.softplus(p["lam"].astype(jnp.float32))
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    b = mult * (i * xa.astype(jnp.float32))
    return a, b                                     # (B,S,W) each, f32


def rglru_scan(p, xa, h0):
    """Linear recurrence h_t = a_t h_{t-1} + b_t via associative scan."""
    a, b = _rglru_coeffs(p, xa)
    if h0 is not None:
        # fold initial state into the first step: b_0 <- b_0 + a_0 * h0
        b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, b1 * a2 + b2

    acc_a, acc_b = lax.associative_scan(combine, (a, b), axis=1)
    return acc_b, acc_b[:, -1]                       # h over seq, final state


def rglru_step(p, xa, h_prev):
    """Single decode step. xa: (B,1,W); h_prev: (B,W) f32."""
    a, b = _rglru_coeffs(p, xa)
    h = a[:, 0] * h_prev + b[:, 0]
    return h[:, None], h


def rglru_block_apply(cfg: ModelConfig, p, x, mode, cache):
    dt = L.cdtype(cfg)
    xb = x.astype(dt)
    xa = constrain(jnp.einsum("bsd,dw->bsw", xb, p["w_x"].astype(dt)),
                   "batch", None, "rnn_width")
    xg = constrain(jnp.einsum("bsd,dw->bsw", xb, p["w_gate"].astype(dt)),
                   "batch", None, "rnn_width")
    if mode == "decode":
        xa, conv_cache = causal_conv1d_step(xa, cache["conv"], p["conv_w"], p["conv_b"])
        h_seq, h_last = rglru_step(p, xa, cache["h"])
        new_cache = {"h": h_last, "conv": conv_cache}
    else:
        pre_tail = xa[:, -3:]                          # conv width 4 -> keep 3
        xa = causal_conv1d(xa, p["conv_w"], p["conv_b"])
        h_seq, h_last = rglru_scan(p, xa, None)
        new_cache = None
        if mode == "prefill":
            pad = 3 - pre_tail.shape[1]
            if pad > 0:
                pre_tail = jnp.pad(pre_tail, ((0, 0), (pad, 0), (0, 0)))
            new_cache = {"h": h_last, "conv": pre_tail.astype(dt)}
    y = (h_seq.astype(dt)) * jax.nn.gelu(xg, approximate=True)
    out = jnp.einsum("bsw,wd->bsd", y, p["w_out"].astype(dt))
    return out, new_cache


# --------------------------------------------------------------------------
# Mamba2 SSD block [arXiv:2405.21060]
# --------------------------------------------------------------------------

def init_ssd(cfg: ModelConfig, key):
    d, di, n, h = cfg.d_model, cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    zxbcdt = 2 * di + 2 * n + h
    return {
        "w_in": (jax.random.normal(ks[0], (d, zxbcdt)) * s).astype(jnp.float32),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, di + 2 * n)) * 0.1
                   ).astype(jnp.float32),
        "conv_b": jnp.zeros((di + 2 * n,), jnp.float32),
        "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[2], (h,),
                                       minval=jnp.log(1e-3), maxval=jnp.log(1e-1))))),
        "norm_scale": jnp.zeros((di,), jnp.float32),
        "w_out": (jax.random.normal(ks[3], (di, d)) * di ** -0.5).astype(jnp.float32),
    }


def _segsum(x):
    """x: (..., q) log-decays -> (..., q, q) lower-tri cumulative segment sums."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(xh, dt, A, Bm, Cm, initial_state=None, chunk: int = 128):
    """SSD forward (chunked dual form).

    xh: (b, s, h, p)  dt: (b, s, h)  A: (h,)  Bm, Cm: (b, s, n) (single group)
    Returns y: (b, s, h, p), final_state: (b, h, p, n). f32 internal.
    """
    b, s, h, p = xh.shape
    n = Bm.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, f"seq {s} % chunk {q}"
    nc = s // q

    f32 = jnp.float32
    xh, dt, Bm, Cm = (t.astype(f32) for t in (xh, dt, Bm, Cm))
    xdt = xh * dt[..., None]                                  # (b,s,h,p)
    dA = dt * A.astype(f32)                                   # (b,s,h) log decay

    def ch(t, tail):
        return t.reshape((b, nc, q) + tail)

    xdt_c = ch(xdt, (h, p))
    dA_c = jnp.transpose(ch(dA, (h,)), (0, 3, 1, 2))          # (b,h,nc,q)
    B_c, C_c = ch(Bm, (n,)), ch(Cm, (n,))
    dA_cs = jnp.cumsum(dA_c, axis=-1)                         # (b,h,nc,q)

    # intra-chunk (diagonal blocks)
    Lmat = jnp.exp(_segsum(dA_c))                             # (b,h,nc,q,q)
    scores = jnp.einsum("bcln,bcsn->bcls", C_c, B_c)          # (b,nc,q,q)
    y_diag = jnp.einsum("bcls,bhcls,bcshp->bclhp", scores, Lmat, xdt_c)

    # per-chunk contributed states
    decay_states = jnp.exp(dA_cs[..., -1:] - dA_cs)           # (b,h,nc,q)
    states = jnp.einsum("bcsn,bhcs,bcshp->bchpn", B_c, decay_states, xdt_c)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cs[..., -1])                     # (b,h,nc)
    s0 = (jnp.zeros((b, h, p, n), f32) if initial_state is None
          else initial_state.astype(f32))

    def scan_fn(carry, inp):
        st_c, dec_c = inp                                     # (b,h,p,n), (b,h)
        new = carry * dec_c[..., None, None] + st_c
        return new, carry                                     # emit state at chunk start

    final, prev_states = lax.scan(
        scan_fn, s0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, -1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)             # (b,nc,h,p,n)

    # contribution of carried state to each step
    state_decay = jnp.exp(dA_cs)                              # (b,h,nc,q)
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", C_c, prev_states, state_decay)

    y = (y_diag + y_off).reshape(b, s, h, p)
    return y, final


def ssd_step(xh, dt, A, Bm, Cm, state):
    """Single decode step. xh: (b,h,p), dt: (b,h), Bm/Cm: (b,n), state: (b,h,p,n)."""
    f32 = jnp.float32
    xh, dt, Bm, Cm, state = (t.astype(f32) for t in (xh, dt, Bm, Cm, state))
    decay = jnp.exp(dt * A.astype(f32))                       # (b,h)
    upd = jnp.einsum("bhp,bn->bhpn", xh * dt[..., None], Bm)
    state = state * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", state, Cm)
    return y, state


def ssd_block_apply(cfg: ModelConfig, p, x, mode, cache):
    dt_ = L.cdtype(cfg)
    b, s, d = x.shape
    di, n, h, ph = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    zxbcdt = jnp.einsum("bsd,de->bse", x.astype(dt_), p["w_in"].astype(dt_))
    z, xc, Bm, Cm, dtr = jnp.split(zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n],
                                   axis=-1)
    xbc = jnp.concatenate([xc, Bm, Cm], axis=-1)
    new_conv = None
    if mode == "decode":
        xbc, new_conv = causal_conv1d_step(xbc, cache["conv"], p["conv_w"], p["conv_b"])
    else:
        pre_conv_tail = xbc[:, -(cfg.ssm_conv - 1):]
        xbc = causal_conv1d(xbc, p["conv_w"], p["conv_b"])
        if mode == "prefill":
            tail = pre_conv_tail
            pad = (cfg.ssm_conv - 1) - tail.shape[1]
            if pad > 0:
                tail = jnp.pad(tail, ((0, 0), (pad, 0), (0, 0)))
            new_conv = tail.astype(dt_)
    xbc = jax.nn.silu(xbc)
    xc, Bm, Cm = jnp.split(xbc, [di, di + n], axis=-1)
    xh = xc.reshape(b, s, h, ph)
    dtv = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"])   # (b,s,h)
    A = -jnp.exp(p["A_log"])

    if mode == "decode":
        y, state = ssd_step(xh[:, 0], dtv[:, 0], A, Bm[:, 0], Cm[:, 0],
                            cache["state"])
        y = y[:, None]
        new_cache = {"state": state, "conv": new_conv}
    else:
        init_state = None
        y, state = ssd_chunked(xh, dtv, A, Bm, Cm, init_state)
        new_cache = {"state": state, "conv": new_conv} if mode == "prefill" else None

    y = y + xh.astype(jnp.float32) * p["D"][:, None]
    y = y.reshape(b, s, di).astype(dt_)
    # gated RMSNorm (mamba2): norm(y * silu(z))
    y = L.rmsnorm(y * jax.nn.silu(z), p["norm_scale"])
    y = constrain(y, "batch", None, "ssm_inner")
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"].astype(dt_))
    return out, new_cache


# --------------------------------------------------------------------------
# unified block init/apply
# --------------------------------------------------------------------------

def init_block(cfg: ModelConfig, kind: str, key, dense: bool = False):
    """``dense``: a leading dense layer of a MoE model (its MLP is of width
    ``d_ff``)."""
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"norm1": L.init_norm(cfg, k1)}
    if kind in ("attn", "local_attn") and cfg.mla is not None:
        p["attn"] = L.init_mla(cfg, k2)
    elif kind in ("attn", "local_attn"):
        p["attn"] = L.init_attention(cfg, k2)
    elif kind == "rglru":
        p["rec"] = init_rglru(cfg, k2)
    elif kind == "ssd":
        p["ssd"] = init_ssd(cfg, k2)
    else:
        raise ValueError(kind)
    if kind != "ssd" and cfg.d_ff:
        p["norm2"] = L.init_norm(cfg, k3)
        if dense:
            p["mlp"] = L.init_mlp(cfg.with_(moe=None), k3)
        elif cfg.moe is not None and cfg.moe.router == "sigmoid_bias":
            p["mlp"] = L.init_moe_held(cfg, k3)
        else:
            p["mlp"] = L.init_mlp(cfg, k3)
    return p


def mla_block_sub_apply(cfg: ModelConfig, p, h, positions, mode, cache):
    """MLA's counterpart of ``attn_block_sub_apply``: the cache holds one
    latent entry per token ("c"), and every mode runs the absorbed
    attention over the latents. Decode and chunk modes emit the tokens'
    new entries as {"c_new"}; prefill returns the padded contiguous
    cache."""
    lat = L.mla_latent(cfg, p, h, positions)               # (b, s, width)
    if mode == "decode":
        dt = cache["c"].dtype
        lat_att = jnp.concatenate([cache["c"], lat.astype(dt)], axis=1)
        if positions.ndim == 2:
            pos_att = jnp.concatenate([cache["pos"], positions], axis=1)
        else:
            pos_att = jnp.concatenate([cache["pos"], positions[0][None]],
                                      axis=0)
        out = L.mla_apply(cfg, p, h, positions, lat_att, pos_att)
        return out, {"c_new": lat.astype(dt)}
    if mode == "chunk":
        # as attn_block_sub_apply: the chunk's entries land at their
        # absolute positions in the identity-indexed view
        dt = cache["c"].dtype
        w = cache["c"].shape[1]
        c = positions.shape[1]
        tgt = jnp.where(jnp.arange(c)[None, :] < cache["cl"][:, None],
                        positions, w)
        lat_att = jax.vmap(lambda cc, ti, ln: cc.at[ti].set(ln, mode="drop"))(
            cache["c"], tgt, lat.astype(dt))
        out = L.mla_apply(cfg, p, h, positions, lat_att, cache["pos"])
        return out, {"c_new": lat.astype(dt)}
    out = L.mla_apply(cfg, p, h, positions, lat, positions)
    new_cache = None
    if mode == "prefill":
        cache_len = cache["c"].shape[1]
        s = lat.shape[1]
        assert s <= cache_len, (s, cache_len)
        pad = cache_len - s
        new_cache = {
            "c": jnp.pad(lat, ((0, 0), (0, pad), (0, 0))).astype(
                cache["c"].dtype),
            "pos": jnp.pad(positions, (0, pad), constant_values=INVALID_POS
                           ).astype(jnp.int32)}
    return out, new_cache


def attn_block_sub_apply(cfg: ModelConfig, kind: str, p, h, positions, mode, cache):
    """Decode-mode cache protocol: the scan emits only the tiny per-layer
    (k_new, v_new) update record; the full cache write happens ONCE after
    the scan (transformer.apply_cache_updates). Passing the big cache
    through the scan's ys restacked it every step (and XLA's convert
    motion did so in f32 — 2x decode cache memory on the dry-run).
    Attention reads [old cache ++ new kv]; the stale slot being overwritten
    is masked out automatically (invalid/rotated-out position)."""
    window = cfg.window if kind == "local_attn" else 0
    if "attend" in (cache or {}):
        # decode / chunk over the paged pool by the attention kernel: the
        # tokens' fresh K/V join inside ``attend``, no dense view is built;
        # the read's integrity verdict travels in the update record
        k_new, v_new = L.project_kv(cfg, p, h, positions)
        dt = jnp.dtype(cfg.dtype)
        k_new, v_new = k_new.astype(dt), v_new.astype(dt)
        out, ok = L.attention_apply(
            cfg, p, h, positions, window=window,
            attend=lambda q: cache["attend"](q, k_new, v_new))
        return out, {"k_new": k_new, "v_new": v_new, "ok": ok}
    if mode == "decode":
        k_new, v_new = L.project_kv(cfg, p, h, positions)
        dt = cache["k"].dtype
        k_att = jnp.concatenate([cache["k"], k_new.astype(dt)], axis=1)
        v_att = jnp.concatenate([cache["v"], v_new.astype(dt)], axis=1)
        if positions.ndim == 2:
            # paged serving path: per-slot positions (B, 1) and per-slot
            # key positions (B, cache_len) -> batched (B, 1, L+1) mask
            pos_att = jnp.concatenate([cache["pos"], positions], axis=1)
        else:
            pos_att = jnp.concatenate([cache["pos"], positions[0][None]],
                                      axis=0)
        out, _ = L.attention_apply(
            cfg, p, h, positions, window=window,
            kv_override=(k_att, v_att, pos_att))
        update = {"k_new": k_new.astype(dt), "v_new": v_new.astype(dt)}
        return out, update
    if mode == "chunk":
        # Chunked prefill over the paged view: the dense view is
        # identity-indexed (view index == absolute position), so scattering
        # the chunk's fresh K/V at their positions reproduces the exact
        # layout of a contiguous prefill padded to the view width — the
        # attention reduction is bitwise identical to the one-shot path.
        # Rows are ragged: row i holds cache["cl"][i] real tokens; padded
        # tokens scatter to a dropped out-of-bounds index.
        k_new, v_new = L.project_kv(cfg, p, h, positions)
        dt = cache["k"].dtype
        w = cache["k"].shape[1]
        c = positions.shape[1]
        tgt = jnp.where(jnp.arange(c)[None, :] < cache["cl"][:, None],
                        positions, w)                         # (B, C)
        k_att = jax.vmap(lambda ck, ti, kn: ck.at[ti].set(kn, mode="drop"))(
            cache["k"], tgt, k_new.astype(dt))
        v_att = jax.vmap(lambda cv, ti, vn: cv.at[ti].set(vn, mode="drop"))(
            cache["v"], tgt, v_new.astype(dt))
        out, _ = L.attention_apply(
            cfg, p, h, positions, window=window,
            kv_override=(k_att, v_att, cache["pos"]))
        update = {"k_new": k_new.astype(dt), "v_new": v_new.astype(dt)}
        return out, update
    impl = "blockwise" if (mode == "prefill" and h.shape[1] > 8192) else "naive"
    out, (k, v) = L.attention_apply(cfg, p, h, positions, window=window, impl=impl)
    new_cache = None
    if mode == "prefill":
        cache_len = cache["k"].shape[1]
        s = k.shape[1]
        if s >= cache_len:
            # keep the last cache_len entries, placed at slot = pos % cache_len
            # (ring-buffer invariant shared with the decode write path)
            shift = (s - cache_len) % cache_len
            ks = jnp.roll(k[:, -cache_len:], shift, axis=1)
            vs = jnp.roll(v[:, -cache_len:], shift, axis=1)
            ps = jnp.roll(positions[-cache_len:], shift)
        else:
            pad = cache_len - s
            ks = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vs = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            ps = jnp.pad(positions, (0, pad), constant_values=INVALID_POS)
        new_cache = {"k": ks.astype(cache["k"].dtype),
                     "v": vs.astype(cache["v"].dtype),
                     "pos": ps.astype(jnp.int32)}
    return out, new_cache


def block_apply(cfg: ModelConfig, kind: str, p, x, positions, mode, cache):
    """Returns (x_out, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(cfg, p["norm1"], x)
    if kind in ("attn", "local_attn") and cfg.mla is not None:
        sub, new_cache = mla_block_sub_apply(cfg, p["attn"], h, positions,
                                             mode, cache)
    elif kind in ("attn", "local_attn"):
        sub, new_cache = attn_block_sub_apply(cfg, kind, p["attn"], h, positions,
                                              mode, cache)
    elif kind == "rglru":
        sub, new_cache = rglru_block_apply(cfg, p["rec"], h, mode, cache)
    elif kind == "ssd":
        sub, new_cache = ssd_block_apply(cfg, p["ssd"], h, mode, cache)
    else:
        raise ValueError(kind)
    x = x + sub.astype(x.dtype)
    routes = None
    if kind != "ssd" and cfg.d_ff:
        h2 = L.apply_norm(cfg, p["norm2"], x)
        if "router" not in p["mlp"]:
            m = L.mlp_apply(cfg, p["mlp"], h2)       # incl. leading dense
        elif cfg.moe.router == "sigmoid_bias":
            # the tokens the route counters count: a chunk's real prompt
            # tokens, the slots that decode
            live = None
            if mode == "chunk":
                live = (jnp.arange(h2.shape[1])[None, :]
                        < cache["cl"][:, None])
            elif mode == "decode":
                live = cache.get("live")
            m, routes = L.moe_held(cfg, p["mlp"], h2, live)
        else:
            if mode == "decode":
                # dropless dense path: exact for tiny decode token counts
                m, aux = L.moe_apply_dense(cfg, p["mlp"], h2)
            else:
                m, aux = L.moe_apply(cfg, p["mlp"], h2)
        x = x + m.astype(x.dtype)
    if (mode in ("decode", "chunk") and cfg.moe is not None
            and cfg.moe.router == "sigmoid_bias"):
        # the update record carries the layer's route counters (zero for a
        # leading dense layer), summed into the serving counters
        new_cache = dict(new_cache, routes=(
            routes if routes is not None else jnp.zeros((2,), jnp.uint32)))
    # sequence-parallel residual stream (Megatron-SP): the scan carry —
    # which the bwd pass stacks per layer — shards its seq dim over
    # `model` when the run enables the "seq_res" rule. 16x smaller
    # activation stacks on the 16x16 mesh.
    x = constrain(x, "batch", "seq_res", None)
    return x, new_cache, aux
