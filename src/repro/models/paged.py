"""Paged KV cache passes: batched decode / ragged prefill over block pools.

The continuous serving path keeps every layer's KV cache in a shared pool of
fixed-size blocks (``cache.paged_pool_init``), indexed per request slot
through a block table. Blocks hold raw u32 words; when a ``CacheSeal`` is
supplied they are **sealed** — XORed with a ChaCha20 keystream derived from
(pool block address, per-block write counter, layer id) by
``kernels.ref.cache_block_otp``, the cache analogue of the weight tiles'
``tile_counters`` scheme:

* **write** (prefill, or the per-step token append): payload is sealed
  before it is stored, and every write to a block bumps its write counter —
  the decode append decrypts the tail block, inserts the token, re-encrypts
  the whole block under ``wc+1`` (ColoE-style write-back), so a (key, nonce,
  counter) triple never covers two plaintexts;
* **read** (attention): a global attention layer over K/V pools hands
  the whole stacked pool to the paged attention kernel
  (``kernels/sealed_paged_attention.py``), which walks each slot's blocks
  only to its length and verifies, unseals and attends them in VMEM; the
  plaintext never reaches HBM. MLA's latent pool and sliding-window layers
  read a dense view instead (``_dense_view``): blocks gathered through the
  table and unsealed in-graph at the consumption site. Either way the pool
  itself, the HBM-resident cache image, stays ciphertext.

Entries at positions >= the slot's length never reach the attention (an
uninitialized sealed block decrypts to random bits, which may be NaN
payloads in bf16); the sealed and plaintext paths feed the attention
bitwise-identical inputs, so their token streams agree exactly.

The host side (write-counter mirror, block allocation, slot scheduling)
lives in ``serve/engine.py``; everything here is pure and jit-friendly.
"""
from __future__ import annotations

import functools
import math
import operator
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.config import ModelConfig
from repro.kernels import ops as KO
from repro.kernels import ref as KR
from repro.kernels.sealed_paged_attention import MASKED
from repro.models import blocks as B
from repro.models import cache as MC
from repro.models import layers as L
from repro.models import transformer as T
from repro.core.sealed_store import CacheSeal


def _otp(seal: CacheSeal, stream: str, block_ids, wcs, lids, wpb: int):
    """The keystream of stream ``stream``'s blocks: K and V in the plane
    order the paged attention kernel unseals in, MLA's latent stream in
    block order (``ref.cache_block_otp``)."""
    return KR.cache_block_otp(seal.key_words, seal.nonce(stream), block_ids,
                              wcs, lids, wpb, planes=stream != "c")


def _flat(blocks, lead: int):
    """(*lead, *block_shape) pool blocks as (*lead, words_per_block)."""
    return blocks.reshape(blocks.shape[:lead] + (-1,))


def kernel_reads(cfg: ModelConfig, kind: str) -> bool:
    """Whether the paged attention kernel serves this layer: global
    attention over K/V pools. MLA's latent pool and sliding-window layers
    read ``_dense_view``."""
    return kind == "attn" and cfg.mla is None


def _dense_view(cfg: ModelConfig, seal: Optional[CacheSeal], pool_j,
                tables, lengths, wc, pos_len=None):
    """Gather one layer's blocks into the dense cache view the decode
    attention consumes: {"k","v","pos"}, or {"c","pos"} for MLA's latent
    pool.

    pool_j: one super-block slice {"k","v": (NB, *block_shape) u32,
    "lid": ()} (or {"c": ...}). tables: (B, MB) int32 pool block ids;
    lengths: (B,) int32; wc: (NB,) u32.
    Returns (view, ok): k/v (B, L, kv_heads, head_dim) (c (B, L, width))
    with L = MB * block_size, pos (B, L) int32 (INVALID_POS beyond each
    slot's length), and ok (B,) bool — per-slot integrity verdict. When the
    seal carries a MAC context, every *resident* gathered block (table
    entries covering positions < length; uninitialized tail blocks are
    skipped) has its Carter–Wegman tag recomputed over the gathered
    CIPHERTEXT — before the unseal XOR, so the check authenticates exactly
    the HBM image — and compared against the co-located ``mac_*`` words.
    ok is all-True when verification is off.

    pos_len (B,) optionally extends the *position* validity past ``lengths``
    for the chunked-prefill path, which splices the chunk's fresh entries
    into the zeroed tail of this view at their absolute positions — entry j
    is a real key for j < pos_len even though only j < lengths came from
    the pool.
    """
    streams = MC.pool_streams(cfg)
    with jax.named_scope("kv_view"):
        b, mb = tables.shape
        with jax.named_scope("kv_gather"):
            words = {s: _flat(pool_j[s][tables], 2)
                     for s in streams}                  # (B, MB, wpb)
            wcb = wc[tables] if seal is not None else None
        wpb = words[streams[0]].shape[-1]
        bs = wpb // MC.kv_words_per_token(cfg)
        seq = mb * bs
        ok = jnp.ones((b,), bool)
        if seal is not None:
            if seal.mac is not None:
                with jax.named_scope("kv_mac"):
                    tags = [seal.mac.tags(words[s], tables, wcb,
                                          pool_j["lid"], tweak=seal.nonce(s))
                            for s in streams]
                    resident = (jnp.arange(mb, dtype=jnp.int32)[None, :]
                                < ((lengths + bs - 1) // bs)[:, None])
                    okb = functools.reduce(operator.and_, [
                        t == pool_j[f"mac_{s}"][tables]
                        for t, s in zip(tags, streams)])
                    ok = jnp.all((~resident) | okb, axis=1)
            with jax.named_scope("kv_unseal"):
                for s in streams:
                    words[s] = words[s] ^ _otp(seal, s, tables, wcb,
                                               pool_j["lid"], wpb)
        with jax.named_scope("kv_mask"):
            dt = jnp.dtype(cfg.dtype)
            shape = (b, seq) + MC.token_shape(cfg)
            view = {s: MC.words_to_kv(words[s], dt).reshape(shape)
                    for s in streams}
            pos = jnp.arange(seq, dtype=jnp.int32)[None, :]
            valid = pos < lengths[:, None]             # (B, L)
            vmask = valid[(Ellipsis,) + (None,) * (len(shape) - 2)]
            for s in streams:
                view[s] = jnp.where(vmask, view[s], 0)
            vpos = valid if pos_len is None else pos < pos_len[:, None]
            view["pos"] = jnp.where(vpos, pos, MC.INVALID_POS)
        return view, ok


def _layer_inputs(cfg: ModelConfig, pools):
    """What the layer scan slices per layer: the pool of a layer that reads
    a dense view; the layer index, layer id and tags of a layer the kernel
    serves — its K/V stay whole, read in place by (layer, block)."""
    n = cfg.n_superblocks()
    return tuple(
        {"layer": jnp.arange(n, dtype=jnp.int32), "lid": pj["lid"],
         "mac_k": pj["mac_k"], "mac_v": pj["mac_v"]}
        if kernel_reads(cfg, kind) else pj
        for kind, pj in zip(cfg.pattern, pools))


def _pool_attention(cfg: ModelConfig, seal: Optional[CacheSeal], pool_j,
                    per, tables, lengths, wc, n_fresh):
    """The kernel-served counterpart of ``_dense_view``: returns
    ``attend(q, k_new, v_new)``, which gives the attention output
    (B, C, heads, head_dim) of the C queries of each row over the row's
    pool prefix [0, lengths) and its C fresh keys, causal among themselves
    and real for j < n_fresh, and ok (B,), the per-row verdict over every
    resident block, all-True without verification.

    pool_j: the layer position's whole stacked pools; per: this layer's
    {"layer", "lid", "mac_k", "mac_v"} slice."""
    blk = pool_j["k"].shape[-2:]
    wpb = blk[0] * blk[1]
    block_size = wpb // MC.kv_words_per_token(cfg)
    words = lambda x: np.asarray(x, np.uint32).view(np.int32)
    wcb = expect = hash_keys = None
    meta = jnp.zeros((16,), jnp.int32)
    meta = meta.at[0].set(per["layer"])
    if seal is not None:
        meta = meta.at[1].set(lax.bitcast_convert_type(per["lid"], jnp.int32))
        meta = meta.at[2:10].set(lax.bitcast_convert_type(
            jnp.asarray(seal.key_words, jnp.uint32), jnp.int32))
        meta = meta.at[10:16].set(jnp.asarray(
            words(seal.nonce("k") + seal.nonce("v"))))
        wcb = wc[tables]
        if seal.mac is not None:
            expect = jnp.stack([
                per[f"mac_{s}"][tables] ^ seal.mac.pads(
                    tables, wcb, per["lid"], tweak=seal.nonce(s))
                for s in ("k", "v")])
            hash_keys = seal.mac.hash_keys(wpb).reshape(wpb, 2).T.reshape(
                (2,) + blk)

    def attend(q, k_new, v_new):
        with jax.named_scope("paged_attention"):
            acc, m, l, bad = KO.paged_attention(
                q, pool_j["k"], pool_j["v"], tables, lengths, meta,
                block_size=block_size, scale=cfg.head_dim ** -0.5,
                softcap=cfg.attn_softcap, wcb=wcb, expect=expect,
                hash_keys=hash_keys)
            return (_merge_fresh(cfg, q, k_new, v_new, acc, m, l, n_fresh),
                    ~bad)

    return attend


def _merge_fresh(cfg: ModelConfig, q, k_new, v_new, acc, m, l, n_fresh):
    """Softmax over [pool prefix ; fresh keys]: the kernel's unnormalised
    partial (acc, m, l) joined with the queries' scores against the C fresh
    keys: query i sees fresh key j for j <= i and j < n_fresh."""
    b, c, h, d = q.shape
    hkv = k_new.shape[2]
    dt = q.dtype
    qg = q.reshape(b, c, hkv, h // hkv, d)
    s = jnp.einsum("bqkgd,bckd->bkgqc", qg, k_new,
                   preferred_element_type=jnp.float32) * cfg.head_dim ** -0.5
    s = L.softcap(s, cfg.attn_softcap)
    i = jnp.arange(c)
    mask = ((i[None, :] <= i[:, None])[None]
            & (i[None, None, :] < n_fresh[:, None, None]))   # (B, Cq, Ck)
    s = jnp.where(mask[:, None, None], s, MASKED)
    split = lambda x: jnp.moveaxis(x.reshape((b, c, hkv, h // hkv)
                                             + x.shape[3:]), 1, 3)
    m, l, acc = split(m), split(l), split(acc)         # (B, hkv, g, Cq, ...)
    m_all = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_all[..., None])
    alpha = jnp.exp(m - m_all)
    den = l * alpha + jnp.sum(p, axis=-1)
    out = acc * alpha[..., None] + jnp.einsum(
        "bkgqc,bckd->bkgqd", p.astype(dt), v_new,
        preferred_element_type=jnp.float32)
    out = out / den[..., None]
    return jnp.moveaxis(out, 3, 1).reshape(b, c, h, d).astype(dt)


def _attention_input(cfg, seal, pools, j, kind, per, tables, lengths, wc,
                     kv_len, n_fresh, pos_len=None):
    """The cache input of layer position j's block: the kernel's ``attend``
    or the dense view, and the view's integrity verdict (None for the
    kernel, whose verdict comes back in the block's update record)."""
    if kernel_reads(cfg, kind):
        return {"attend": _pool_attention(cfg, seal, pools[j], per, tables,
                                          kv_len, wc, n_fresh)}, None
    return _dense_view(cfg, seal, per, tables, lengths, wc, pos_len=pos_len)


def _verdict(up, ok):
    """The update record without the kernel's verdict, and the layer's
    verdict: the view's ``ok``, else the one the block returned."""
    if ok is not None:
        return up, ok
    up = dict(up)
    return up, up.pop("ok")


def decode_logits(cfg: ModelConfig, params, pools, tables, lengths, wc,
                  tokens, seal: Optional[CacheSeal], live=None):
    """One decode step for every slot at its own position.

    tokens: (B, 1) int32 (garbage for inactive slots — masked by lengths).
    live: (B, 1) bool, the slots that decode (what a MoE layer counts, and
    the rows the paged attention kernel walks).
    Returns (logits (B, V) f32, updates: per-position {"k_new","v_new"}
    ({"c_new"} for MLA, and a MoE model's per-layer "routes" counters)
    stacked (n_super, B, 1, ...), ok (B,) bool — the AND of
    every layer's cache-read integrity verdict; all-True unless the seal
    carries a MAC context).
    """
    x = T._embed(cfg, params, {"tokens": tokens})
    positions = lengths[:, None].astype(jnp.int32)          # (B, 1)
    kv_len = lengths if live is None else jnp.where(live[:, 0], lengths, 0)
    ones = jnp.ones_like(lengths)

    def body(h, xs):
        p_slices, per = xs
        ups, oks = [], []
        for j, kind in enumerate(cfg.pattern):
            cache, okj = _attention_input(cfg, seal, pools, j, kind, per[j],
                                          tables, lengths, wc, kv_len, ones)
            if live is not None:
                cache["live"] = live
            h, up, _ = B.block_apply(cfg, kind, p_slices[j], h, positions,
                                     "decode", cache)
            up, okj = _verdict(up, okj)
            ups.append(up)
            oks.append(okj)
        return h, (tuple(ups), jnp.all(jnp.stack(oks), axis=0))

    x, (updates, oks) = T.scan_layers(cfg, params, body, x,
                                      _layer_inputs(cfg, pools))
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = T._unembed(cfg, params, x)[:, 0]
    return logits, updates, jnp.all(oks, axis=0)


def chunk_logits(cfg: ModelConfig, params, pools, tables, lengths, wc,
                 tokens, chunk_len, seal: Optional[CacheSeal]):
    """One chunked-prefill pass: row i holds ``chunk_len[i]`` prompt tokens
    at absolute positions [lengths[i], lengths[i] + chunk_len[i]).

    A kernel-served layer attends each chunk token over the row's pool
    prefix and the chunk's own keys, causally (``_pool_attention``). A
    view-served layer runs over the paged view with the chunk's fresh
    K/V spliced in at their absolute positions ("chunk" mode in
    ``blocks.block_apply``) — every key sits at view index == position, the
    exact layout of a contiguous prefill. Returns (logits (B, V) at each
    row's last chunk token, updates: per layer {"k_new","v_new"} (or
    {"c_new"}) stacked (n, B, C, ...) for ``append_tokens`` to seal into
    the pools, ok (B,) bool — per-slot cache-read integrity verdict across
    all layers).
    """
    x = T._embed(cfg, params, {"tokens": tokens})
    c = tokens.shape[1]
    positions = (lengths[:, None]
                 + jnp.arange(c, dtype=jnp.int32)[None, :])     # (B, C)
    kv_len = jnp.where(chunk_len > 0, lengths, 0)

    def body(h, xs):
        p_slices, per = xs
        ups, oks = [], []
        for j, kind in enumerate(cfg.pattern):
            cache, okj = _attention_input(cfg, seal, pools, j, kind, per[j],
                                          tables, lengths, wc, kv_len,
                                          chunk_len,
                                          pos_len=lengths + chunk_len)
            cache["cl"] = chunk_len
            h, up, _ = B.block_apply(cfg, kind, p_slices[j], h, positions,
                                     "chunk", cache)
            up, okj = _verdict(up, okj)
            ups.append(up)
            oks.append(okj)
        return h, (tuple(ups), jnp.all(jnp.stack(oks), axis=0))

    x, (updates, oks) = T.scan_layers(cfg, params, body, x,
                                      _layer_inputs(cfg, pools))
    x = L.apply_norm(cfg, params["final_norm"], x)
    idx = jnp.maximum(chunk_len - 1, 0)[:, None, None]
    last = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[-1])), axis=1)
    logits = T._unembed(cfg, params, last)[:, 0]
    return logits, updates, jnp.all(oks, axis=0)


def append_tokens(cfg: ModelConfig, seal: Optional[CacheSeal], pools,
                  updates, tables, lengths, counts, wc):
    """Splice each row's ``counts[i]`` new K/V tokens (MLA: latent
    entries) into its blocks at
    positions [lengths[i], lengths[i] + counts[i]) — the unified write path
    for the decode append (C == 1) and the chunked prefill (C == chunk).

    Touched blocks are fetched, unsealed under the current write counter,
    spliced a whole token at a time, and re-sealed under ``wc + 1``;
    ``wc`` is bumped in the returned array (device-resident scheduler
    state — the host keeps only a debug mirror). Rows with counts == 0
    touch nothing: untouched blocks are scattered with dropped
    (out-of-bounds) indices, so masked slots cost no writes and no counter
    bumps. Returns (pools, wc).
    """
    streams = MC.pool_streams(cfg)
    with jax.named_scope("kv_append"):
        wpt = MC.kv_words_per_token(cfg)
        b, mb = tables.shape
        nb = wc.shape[0]
        new_pools = []
        wc_out = wc
        for j in range(len(cfg.pattern)):
            pj, uj = pools[j], updates[j]
            wpb = math.prod(pj[streams[0]].shape[2:])
            bs = wpb // wpt
            c = uj[f"{streams[0]}_new"].shape[2]
            nspan = 1 + (c + bs - 2) // bs     # blocks a chunk write can span
            lid = pj["lid"]
            n = lid.shape[0]
            o = lengths % bs                                     # (B,)
            span = (lengths // bs)[:, None] + jnp.arange(nspan)[None, :]
            span = jnp.minimum(span, mb - 1)
            pb = jnp.take_along_axis(tables, span, axis=1)       # (B, nspan)
            s_id = jnp.arange(nspan)[None, :]
            touched = ((s_id * bs < (o + counts)[:, None])
                       & ((s_id + 1) * bs > o[:, None])
                       & (counts > 0)[:, None])                  # (B, nspan)
            tok = jnp.arange(nspan * bs)                         # window token
            sel = ((tok[None, :] >= o[:, None])
                   & (tok[None, :] < (o + counts)[:, None]))      # (B, ns*bs)
            # the chunk token each window token takes: indices move whole
            # tokens (wpt words), and o + c <= nspan * bs, so none wraps
            src = (tok[None, :] - o[:, None])[None, :, :, None]  # 1,B,ns*bs,1

            def splice(pool_words, mac_words, x_new, s):
                tw = MC.kv_to_words(x_new.reshape(n, b, c, -1))  # (n,B,C,wpt)
                if c > 1:
                    tw = jnp.take_along_axis(tw, src, axis=2, mode="clip")
                blk = pool_words[:, pb]                # (n,B,ns,*block_shape)
                flat = blk.reshape(n, b, nspan * bs, wpt)
                if seal is not None:
                    otp0 = _otp(seal, s, pb, wc[pb], lid[:, None, None], wpb)
                    otp1 = _otp(seal, s, pb, wc[pb] + 1, lid[:, None, None],
                                wpb)
                    flat = flat ^ otp0.reshape(flat.shape)
                # with C == 1 the one token broadcasts and sel picks offset o
                out = jnp.where(sel[None, :, :, None], tw, flat)
                if seal is not None:
                    out = out ^ otp1.reshape(flat.shape)
                out = out.reshape(n, b, nspan, wpb)
                out = jnp.where(touched[None, :, :, None], out,
                                _flat(blk, 3))
                tgt = jnp.where(touched, pb, nb)       # untouched -> dropped
                if seal is not None and seal.mac is not None:
                    # re-MAC the rewritten image under the bumped counter —
                    # tags of untouched rows land on dropped indices
                    tags = seal.mac.tags(out, pb, wc[pb] + 1,
                                         lid[:, None, None],
                                         tweak=seal.nonce(s))
                    mac_words = mac_words.at[:, tgt].set(tags, mode="drop")
                return (pool_words.at[:, tgt].set(out.reshape(blk.shape),
                                                  mode="drop"), mac_words)

            new = {}
            for s in streams:
                new[s], new[f"mac_{s}"] = splice(
                    pj[s], pj[f"mac_{s}"], uj[f"{s}_new"], s)
            new_pools.append(dict(new, lid=lid))
            if j == 0:
                tgt = jnp.where(touched, pb, nb)
                wc_out = wc.at[tgt].add(jnp.uint32(1), mode="drop")
        return tuple(new_pools), wc_out


def copy_blocks(cfg: ModelConfig, seal: Optional[CacheSeal], pools, wc,
                src, dst, mask):
    """Copy-on-write: duplicate blocks ``src -> dst`` (both (K,) int32,
    ``mask`` (K,) bool gating padded rows).

    Sealed pools re-key in flight: the payload is unsealed under (src
    address, wc[src]) and re-sealed under (dst address, wc[dst] + 1) — a
    fresh OTP for the copy, no plaintext ever lands in the pool. Returns
    (pools, wc, ok) with the destination counters bumped; ok is a scalar
    bool — when the seal carries a MAC context, every masked source block
    is verified against its stored tag *before* the re-key (a COW must not
    launder a tampered block into a freshly-MACed copy) and the copy gets
    its own tag under the destination (address, counter).
    """
    with jax.named_scope("kv_copy"):
        nb = wc.shape[0]
        tgt = jnp.where(mask, dst, nb)                 # pads -> dropped
        new_pools = []
        oks = []
        streams = MC.pool_streams(cfg)
        for pj in pools:
            lid = pj["lid"]

            def copy(pool_words, mac_words, s):
                blk = pool_words[:, src]              # (n, K, *block_shape)
                shape = blk.shape
                blk = _flat(blk, 2)
                wpb = blk.shape[-1]
                ok = jnp.bool_(True)
                if seal is not None:
                    nonce = seal.nonce(s)
                    if seal.mac is not None:
                        ts = seal.mac.tags(blk, src, wc[src], lid[:, None],
                                           tweak=nonce)
                        ok = jnp.all(~mask[None, :]
                                     | (ts == mac_words[:, src]))
                    blk = blk ^ _otp(seal, s, src, wc[src], lid[:, None], wpb)
                    blk = blk ^ _otp(seal, s, dst, wc[dst] + 1, lid[:, None],
                                     wpb)
                    if seal.mac is not None:
                        td = seal.mac.tags(blk, dst, wc[dst] + 1, lid[:, None],
                                           tweak=nonce)
                        mac_words = mac_words.at[:, tgt].set(td, mode="drop")
                return (pool_words.at[:, tgt].set(blk.reshape(shape),
                                                  mode="drop"),
                        mac_words, ok)

            new, ok = {}, []
            for s in streams:
                new[s], new[f"mac_{s}"], ok_s = copy(
                    pj[s], pj[f"mac_{s}"], s)
                ok.append(ok_s)
            new_pools.append(dict(new, lid=lid))
            oks.append(functools.reduce(operator.and_, ok))
        return (tuple(new_pools), wc.at[tgt].add(jnp.uint32(1), mode="drop"),
                jnp.all(jnp.stack(oks)))


def prefill_logits(cfg: ModelConfig, params, tokens, true_len):
    """Ragged prefill of a right-padded (A, S_bucket) admission batch.

    Returns (logits (A, V) at each row's last real token, contiguous cache
    from ``prefill_hidden`` for ``prefill_write`` to reseal into pools).
    Padding tokens sit at the tail, so causality keeps every real token's
    hidden state independent of them; their cache entries are masked out
    downstream by the slot lengths.
    """
    x, cache = T.prefill_hidden(cfg, params, {"tokens": tokens},
                                tokens.shape[1])
    idx = (true_len.astype(jnp.int32) - 1)[:, None, None]
    last = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[-1])), axis=1)
    logits = T._unembed(cfg, params, last)[:, 0]
    return logits, cache


def prefill_write(cfg: ModelConfig, seal: Optional[CacheSeal], pools, cache,
                  block_tables, wc):
    """Seal a prefill's contiguous cache into pool blocks.

    cache: per pattern position {"k","v": (n, A, S_bucket, h, d)} (MLA:
    {"c": (n, A, S_bucket, width)}).
    block_tables: (A, S_bucket // bs) pool ids — the host bumps the write
    counters of these blocks *before* the call, so the seal uses the passed
    ``wc`` directly. Dummy admission rows carry a zeroed table row and land
    on the scratch block.
    """
    wpt = MC.kv_words_per_token(cfg)
    streams = MC.pool_streams(cfg)
    a, nblk = block_tables.shape
    new_pools = []
    for j in range(len(cfg.pattern)):
        pj, cj = pools[j], cache[j]
        blk = pj[streams[0]].shape[2:]
        wpb = math.prod(blk)
        n, sb = cj[streams[0]].shape[0], cj[streams[0]].shape[2]
        assert sb * wpt == nblk * wpb, (sb, wpt, nblk, wpb)

        def write(pool_words, mac_words, kv, s):
            w = MC.kv_to_words(kv.reshape(n, a, sb, -1))   # (n, A, Sb, wpt)
            w = w.reshape(n, a, nblk, wpb)
            if seal is not None:
                w = w ^ _otp(seal, s, block_tables, wc[block_tables],
                             pj["lid"][:, None, None], wpb)
                if seal.mac is not None:
                    tags = seal.mac.tags(w, block_tables, wc[block_tables],
                                         pj["lid"][:, None, None],
                                         tweak=seal.nonce(s))
                    mac_words = mac_words.at[:, block_tables].set(tags)
            return (pool_words.at[:, block_tables].set(
                w.reshape((n, a, nblk) + blk)), mac_words)

        new = {}
        for s in streams:
            new[s], new[f"mac_{s}"] = write(pj[s], pj[f"mac_{s}"], cj[s], s)
        new_pools.append(dict(new, lid=pj["lid"]))
    return tuple(new_pools)
