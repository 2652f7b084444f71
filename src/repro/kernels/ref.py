"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel test sweeps shapes/dtypes and asserts allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cipher as C


def chacha20_keystream_ref(key_words, nonce_words, counters):
    """(16, N) u32 keystream — word-major, same layout as the kernel."""
    return C.chacha20_words(jnp.asarray(key_words, jnp.uint32),
                            jnp.asarray(counters, jnp.uint32),
                            jnp.asarray(nonce_words, jnp.uint32))


# --------------------------------------------------------------------------
# tile-sealed weight format + fused sealed matmul
# --------------------------------------------------------------------------

def tile_counters(k: int, n: int, bk: int, bn: int, write_counter: int = 0):
    """Counter id and keystream word for every weight word, derived from
    its tile address — the per-word statement of the tile-sealed format.

    Word (i, j) lives in tile t = (i//bk)*(n//bn) + (j//bn) at in-tile row
    r = i % bk and column c = j % bn. A tile's bk*bn/16 ChaCha blocks form a
    (bk/16, bn) plane: block (s, c) has in-tile index s*bn + c, and its
    keystream word w pads row r = w*(bk/16) + s. So the pad of a tile is the
    16 keystream-word planes stacked along rows, which is how the kernel
    builds it. The write_counter is folded in by offsetting the counter
    space (the sealing side bumps it on every rewrite, mirroring ColoE
    write-backs).
    """
    assert bk % 16 == 0, bk
    nn, rpp = n // bn, bk // 16
    ii, jj = np.meshgrid(np.arange(k), np.arange(n), indexing="ij")
    tile_id = (ii // bk) * nn + (jj // bn)
    r, c = ii % bk, jj % bn
    blocks_total = k * n // 16
    ctr = (tile_id.astype(np.int64) * (rpp * bn) + (r % rpp) * bn + c
           + np.int64(write_counter) * blocks_total)
    lane = r // rpp
    return ctr.astype(np.uint32), lane.astype(np.uint32)


def tile_xor(words, key_words, nonce_words, bk: int, bn: int,
             row_mask=None, write_counter=0, dtype=jnp.uint32):
    """(K, N) u32 ``words`` XOR the tile-sealed keystream (``tile_counters``);
    rows where ``row_mask`` is False pass through (SE bypass). Walks one row
    of tiles at a time, so the only transient is one (bk, N) pad; each row
    is bitcast to ``dtype`` as it is written. ``write_counter`` may be
    traced."""
    k, n = words.shape
    assert k % bk == 0 and n % bn == 0 and bk % 16 == 0, (k, n, bk, bn)
    nk, nn, rpp = k // bk, n // bn, bk // 16
    per_row = n * bk // 16                   # ChaCha blocks per tile row
    base = jnp.asarray(write_counter, jnp.uint32) * jnp.uint32(k * n // 16)
    key = jnp.asarray(key_words, jnp.uint32)
    nonce = jnp.asarray(nonce_words, jnp.uint32)
    mask = (jnp.ones((k,), bool) if row_mask is None
            else jnp.asarray(row_mask, bool))

    def row(args):
        ti, w_row, m_row = args
        ctr = (base + ti * jnp.uint32(per_row)
               + jnp.arange(per_row, dtype=jnp.uint32))
        ks = C.chacha20_words(key, ctr, nonce)          # (16, per_row)
        pad = ks.reshape(16, nn, rpp, bn).transpose(0, 2, 1, 3)
        out = jnp.where(m_row, w_row ^ pad.reshape(bk, n), w_row)
        return jax.lax.bitcast_convert_type(out, dtype)

    rows = (jnp.arange(nk, dtype=jnp.uint32),
            jnp.asarray(words, jnp.uint32).reshape(nk, bk, n),
            mask.reshape(nk, bk, 1))
    return jax.lax.map(row, rows).reshape(k, n)


def cache_block_otp(key_words, nonce3, block_ids, write_counters, layer_ids,
                    words_per_block: int, planes: bool = True):
    """Keystream for paged KV-cache blocks — the cache analogue of
    ``tile_counters``: the OTP derives from the block's pool address, its
    write counter and the layer id, so any block seals/unseals independently
    and the (key, nonce, counter) triple is never reused for a given key.

    Derivation per ChaCha block ``c`` of a cache block ``b``:
      counter = b * cpb + c,  cpb = ceil(words_per_block/16)
      nonce   = (nonce3[0] ^ layer_id, nonce3[1] ^ write_counter, nonce3[2])

    Word order: with ``planes`` (K and V), word w of ChaCha block c pads
    element ``w * cpb + c`` — the 16 state words are 16 planes of the block,
    the order in which the paged attention kernel makes the pad in
    registers; without it (MLA's latent stream), element ``c * 16 + w``.

    ``block_ids`` / ``write_counters`` / ``layer_ids`` broadcast together to
    a common shape S; returns a (*S, words_per_block) u32 keystream. XOR
    with the block payload both seals and unseals (involution).
    """
    bid = jnp.asarray(block_ids, jnp.uint32)
    wc = jnp.asarray(write_counters, jnp.uint32)
    lid = jnp.asarray(layer_ids, jnp.uint32)
    shape = jnp.broadcast_shapes(bid.shape, wc.shape, lid.shape)
    bid, wc, lid = (jnp.broadcast_to(t, shape).reshape(-1)
                    for t in (bid, wc, lid))
    cpb = -(-words_per_block // 16)            # ChaCha blocks per cache block
    sub = jnp.arange(cpb, dtype=jnp.uint32)
    ctr = (bid[:, None] * jnp.uint32(cpb) + sub[None, :]).reshape(-1)
    nonces = (jnp.uint32(nonce3[0]) ^ jnp.repeat(lid, cpb),
              jnp.uint32(nonce3[1]) ^ jnp.repeat(wc, cpb),
              nonce3[2])
    key = jnp.asarray(key_words, jnp.uint32)
    if planes:
        ks = C.chacha20_words(key, ctr, nonces).reshape(16, -1, cpb)
        ks = jnp.moveaxis(ks, 0, 1)                # (N, 16, cpb)
    else:
        ks = C.chacha20_block(key, ctr, nonces)
    return ks.reshape(shape + (cpb * 16,))[..., :words_per_block]


def seal_weights_ref(w, key_words, nonce_words, bk: int, bn: int,
                     row_mask=None, write_counter=0):
    """Encrypt a (K, N) f32 weight for the fused kernel.

    Returns u32 ciphertext with the same (K, N) shape. Rows where
    ``row_mask`` is False stay plaintext (SE bypass).
    """
    wu = jax.lax.bitcast_convert_type(w.astype(jnp.float32), jnp.uint32)
    return tile_xor(wu, key_words, nonce_words, bk, bn, row_mask,
                    write_counter)


def unseal_weights_ref(wct, key_words, nonce_words, bk: int, bn: int,
                       row_mask=None, write_counter=0):
    return tile_xor(wct, key_words, nonce_words, bk, bn, row_mask,
                    write_counter, jnp.float32)


def sealed_matmul_ref(x, wct, key_words, nonce_words, bk: int, bn: int,
                      row_mask=None, write_counter=0):
    """Oracle: decrypt the whole weight, then plain matmul."""
    w = unseal_weights_ref(wct, key_words, nonce_words, bk, bn, row_mask,
                           write_counter)
    return jnp.dot(x.astype(jnp.float32), w,
                   preferred_element_type=jnp.float32)


def sealed_gmm_ref(x, wct, key_words, nonce_words, bk: int, bn: int,
                   row_mask, write_counters):
    """Oracle of ``sealed_gmm``: decrypt each expert's weight under its own
    write counter, then a plain batched matmul. x (E, T, K), wct (E, K, N),
    row_mask (E, K), write_counters (E,)."""
    w = jnp.stack([unseal_weights_ref(wct[e], key_words, nonce_words, bk, bn,
                                      row_mask[e], write_counters[e])
                   for e in range(wct.shape[0])])
    return jnp.einsum("etk,ekn->etn", x.astype(jnp.float32), w,
                      preferred_element_type=jnp.float32)
