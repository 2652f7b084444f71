"""jit'd public wrappers over the Pallas kernels (+ faithful unfused
baselines used for before/after comparisons in §Perf).

Interpret mode is chosen here and nowhere else: ``_default_interpret`` is
True only on the CPU backend (tests), never on a TPU. The raw kernels take
``interpret`` as a required argument.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import chacha20 as _cc
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import sealed_gmm as _sg
from repro.kernels import sealed_matmul as _sm
from repro.kernels import sealed_paged_attention as _spa


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


def keystream(key_words, nonce_words, n_blocks: int, *, tile: int = 1024,
              counter0: int = 0, interpret=None):
    """(16, n_blocks) u32 ChaCha20 keystream via the Pallas kernel."""
    interpret = _default_interpret() if interpret is None else interpret
    pad = (-n_blocks) % tile
    ctr = jnp.arange(counter0, counter0 + n_blocks + pad, dtype=jnp.uint32)
    out = _cc.chacha20_keystream(jnp.asarray(key_words, jnp.uint32),
                                 jnp.asarray(nonce_words, jnp.uint32),
                                 ctr, tile=tile, interpret=interpret)
    return out[:, :n_blocks]


def seal_weights(w, key_words, nonce_words, *, bk: int = 128, bn: int = 128,
                 row_mask=None, write_counter: int = 0):
    """Host-side tile-seal of a weight matrix (jnp oracle path)."""
    return _ref.seal_weights_ref(w, key_words, nonce_words, bk, bn,
                                 row_mask, write_counter)


def sealed_matmul(x, w_ct, row_mask, key_words, nonce_words,
                  write_counter=0, *, bm: int = 128, bk: int = 128,
                  bn: int = 128, interpret=None,
                  compute_dtype: str = "float32"):
    """Fused decrypt+matmul (beyond-paper optimization; zero extra HBM).

    K/N must be multiples of (bk, bn) — that's the sealed storage contract;
    the activation dim M is padded here as needed. ``write_counter`` may be
    a traced scalar (the serving path threads it through SealedTensor)."""
    interpret = _default_interpret() if interpret is None else interpret
    wc = jnp.asarray(write_counter, jnp.uint32).reshape(-1)[:1]
    m = x.shape[0]
    bm = min(bm, m) if m % bm else bm
    pad = (-m) % bm
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    out = _sm.sealed_matmul(x, w_ct, row_mask, key_words, nonce_words, wc,
                            bm=bm, bk=bk, bn=bn, interpret=interpret,
                            compute_dtype=compute_dtype)
    return out[:m]


def sealed_gmm(x, w_ct, row_mask, key_words, nonce_words, write_counters, *,
               bk: int = 128, bn: int = 128, interpret=None,
               compute_dtype: str = "float32"):
    """Grouped fused decrypt+matmul over a stack of sealed experts: x
    (E, T, K), w_ct (E, K, N) u32, one write counter per expert. The slab
    rows T are padded here to a multiple of 8."""
    interpret = _default_interpret() if interpret is None else interpret
    t = x.shape[1]
    pad = (-t) % 8
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    out = _sg.sealed_gmm(x, w_ct, row_mask, key_words, nonce_words,
                         write_counters, bk=bk, bn=bn, interpret=interpret,
                         compute_dtype=compute_dtype)
    return out[:, :t]


def flash_attention(q, k, v, *, scale: float, softcap: float = 0.0,
                    window: int = 0, bq: int = 128, bkv: int = 128,
                    interpret=None):
    """Causal flash attention (see ``kernels.flash_attention``)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, scale=scale, softcap=softcap,
                               window=window, bq=bq, bkv=bkv,
                               interpret=interpret)


def paged_attention(q, k_pool, v_pool, tables, lengths, meta, *,
                    block_size: int, scale: float, softcap: float = 0.0,
                    wcb=None, expect=None, hash_keys=None, interpret=None):
    """Attention of q (B, C, H, D) over each row's sealed pool prefix
    ``[0, lengths)`` (``kernels.sealed_paged_attention``). The pools are
    (n, NB, rows, lanes) u32 with the K/V dtype of q; meta, wcb, expect and
    hash_keys as the kernel takes them. GQA: query head h reads kv head
    ``h // (H / kv_heads)``.

    Lays each query head's words out in the lanes its kv head takes in a
    pool row (even and odd elements apart for a 2-byte dtype), and takes
    each head's output back out of the kernel's rows. Returns (acc
    (B, C, H, D) f32 unnormalised, m (B, C, H) running max, l (B, C, H)
    denominator, bad (B,) bool)."""
    interpret = _default_interpret() if interpret is None else interpret
    b, c, h, d = q.shape
    rows, lanes = k_pool.shape[-2:]
    item = q.dtype.itemsize
    dw = d * item // 4                         # words of one head
    hkv = rows * lanes // block_size // dw
    assert lanes % dw == 0 and h % hkv == 0, (k_pool.shape, q.shape)
    kvh = np.arange(h) // (h // hkv)
    slot = (kvh * dw) % lanes // dw            # head's lanes in its row
    quarter = (kvh * dw) // lanes              # its row of the token
    parts = [q[..., 0::2], q[..., 1::2]] if item == 2 else [q]
    onehot = jnp.asarray(slot[:, None] == np.arange(lanes // dw))
    qk = jnp.stack([jnp.where(onehot[:, :, None], p_[..., None, :], 0)
                    for p_ in parts], axis=1)  # (B, parts, C, H, slots, dw)
    qk = qk.reshape(b, len(parts), c * h, lanes)
    acc, m, l, bad = _spa.sealed_paged_attention(
        qk, jnp.asarray(np.tile(quarter, c)[:, None], jnp.int32), k_pool,
        v_pool, lengths, tables, meta, wcb, expect, hash_keys,
        block_size=block_size, kv_dtype=str(q.dtype), scale=scale,
        softcap=softcap, interpret=interpret)
    acc = acc.reshape(b, len(parts), c, h, lanes // dw, dw)
    acc = jnp.take_along_axis(
        acc, jnp.asarray(slot)[None, None, None, :, None, None],
        axis=4)[:, :, :, :, 0]                 # (B, parts, C, H, dw)
    acc = jnp.stack([acc[:, 0], acc[:, 1]], axis=-1) if item == 2 else acc[:, 0]
    return (acc.reshape(b, c, h, d), m.reshape(b, c, h), l.reshape(b, c, h),
            bad)


def decrypt_then_matmul(x, w_ct, row_mask, key_words, nonce_words,
                        write_counter: int = 0, *, bk: int = 128,
                        bn: int = 128):
    """Paper-faithful baseline: decrypt pass first (extra weight round-trip),
    then a plain matmul. Used as the §Perf before/after reference."""
    w = _ref.unseal_weights_ref(w_ct, key_words, nonce_words, bk, bn,
                                row_mask, write_counter)
    return jnp.dot(x.astype(jnp.float32), w, preferred_element_type=jnp.float32)
