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


def decrypt_then_matmul(x, w_ct, row_mask, key_words, nonce_words,
                        write_counter: int = 0, *, bk: int = 128,
                        bn: int = 128):
    """Paper-faithful baseline: decrypt pass first (extra weight round-trip),
    then a plain matmul. Used as the §Perf before/after reference."""
    w = _ref.unseal_weights_ref(w_ct, key_words, nonce_words, bk, bn,
                                row_mask, write_counter)
    return jnp.dot(x.astype(jnp.float32), w, preferred_element_type=jnp.float32)
