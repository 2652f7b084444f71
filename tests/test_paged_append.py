"""``paged.append_tokens`` splices whole tokens: bitwise the word-roll splice.

The write path once shifted the new tokens into the window of touched
blocks by a word-granular roll (a gather of one u32 per index); it now moves
whole tokens. The pool image must not change with it: the ciphertext, the
MAC words and the write counters after an append are compared bit for bit
with the word-roll body, kept here as the oracle, over plaintext, sealed and
sealed + MAC pools, chunk widths 1 to 32, offsets at the start, middle and
end of a block, rows that append nothing, and a row whose span is clamped
at the end of its block table.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import sealed_store as SS
from repro.kernels import ref as KR
from repro.models import cache as MC
from repro.models import paged as PG

BS, MB = 16, 6          # block size (tokens), blocks per slot


def _append_word_roll(cfg, seal, pools, updates, tables, lengths, counts, wc):
    """The splice by a word-level roll (``take_along_axis`` over words)."""
    wpt = MC.kv_words_per_token(cfg)
    b, mb = tables.shape
    nb = wc.shape[0]
    new_pools = []
    wc_out = wc
    for j in range(len(cfg.pattern)):
        pj, uj = pools[j], updates[j]
        shape_k = pj["k"].shape              # (n, NB, *block_shape)
        wpb = math.prod(shape_k[2:])
        bs = wpb // wpt
        c = uj["k_new"].shape[2]
        nspan = 1 + (c + bs - 2) // bs
        lid = pj["lid"]
        n = lid.shape[0]
        o = lengths % bs
        span = (lengths // bs)[:, None] + jnp.arange(nspan)[None, :]
        span = jnp.minimum(span, mb - 1)
        pb = jnp.take_along_axis(tables, span, axis=1)
        s_id = jnp.arange(nspan)[None, :]
        touched = ((s_id * bs < (o + counts)[:, None])
                   & ((s_id + 1) * bs > o[:, None])
                   & (counts > 0)[:, None])
        w2 = nspan * wpb
        widx = jnp.arange(w2)
        tok_of_w = widx // wpt
        sel = ((tok_of_w[None, :] >= o[:, None])
               & (tok_of_w[None, :] < (o + counts)[:, None]))
        roll = (widx[None, :] - (o * wpt)[:, None]) % w2

        def splice(pool_words, mac_words, x_new, nonce):
            tw = MC.kv_to_words(x_new.reshape(n, b, c, -1))
            base = jnp.concatenate(
                [tw.reshape(n, b, c * wpt),
                 jnp.zeros((n, b, w2 - c * wpt), jnp.uint32)], axis=-1)
            rolled = jnp.take_along_axis(
                base, jnp.broadcast_to(roll[None], (n, b, w2)), axis=-1)
            blk = pool_words[:, pb]
            flat = blk.reshape(n, b, w2)
            if seal is not None:
                otp0 = KR.cache_block_otp(seal.key_words, nonce, pb, wc[pb],
                                          lid[:, None, None], wpb)
                otp1 = KR.cache_block_otp(seal.key_words, nonce, pb,
                                          wc[pb] + 1, lid[:, None, None], wpb)
                flat = flat ^ otp0.reshape(n, b, w2)
            out = jnp.where(sel[None], rolled, flat)
            if seal is not None:
                out = out ^ otp1.reshape(n, b, w2)
            out = out.reshape(n, b, nspan, wpb)
            out = jnp.where(touched[None, :, :, None], out, blk)
            tgt = jnp.where(touched, pb, nb)
            if seal is not None and seal.mac is not None:
                tags = seal.mac.tags(out, pb, wc[pb] + 1,
                                     lid[:, None, None], tweak=nonce)
                mac_words = mac_words.at[:, tgt].set(tags, mode="drop")
            return pool_words.at[:, tgt].set(out, mode="drop"), mac_words

        flat = lambda x: x.reshape(x.shape[:2] + (wpb,))
        nk, nmk = splice(flat(pj["k"]), pj["mac_k"], uj["k_new"],
                         seal.nonce_k if seal is not None else None)
        nv, nmv = splice(flat(pj["v"]), pj["mac_v"], uj["v_new"],
                         seal.nonce_v if seal is not None else None)
        new_pools.append({"k": nk.reshape(shape_k), "v": nv.reshape(shape_k),
                          "mac_k": nmk, "mac_v": nmv, "lid": lid})
        if j == 0:
            tgt = jnp.where(touched, pb, nb)
            wc_out = wc.at[tgt].add(jnp.uint32(1), mode="drop")
    return tuple(new_pools), wc_out


def _case(cfg, c, off, rng):
    """Random pools, counters and new K/V for five rows:
    0: a full chunk of c tokens at offset ``off``;
    1: counts == 0 (writes nothing, bumps nothing);
    2: a partial chunk, 1..c tokens at ``off``;
    3: a full chunk at ``off`` in the last block but one;
    4: the last block of the table (span clamped at MB - 1), as many
       tokens as fit in it."""
    b = 5
    nb = 1 + b * MB
    n = cfg.n_superblocks()
    pools = []
    for j, p in enumerate(MC.paged_pool_init(cfg, nb, BS)):
        pools.append({key: (jnp.asarray(rng.randint(0, 2 ** 32, p[key].shape,
                                                     dtype=np.uint64)
                                        .astype(np.uint32))
                            if key != "lid" else p[key])
                      for key in p})
    tables = np.stack([1 + i * MB + rng.permutation(MB) for i in range(b)])
    blk = np.array([1, 2, 0, MB - 3, MB - 1])
    lengths = blk * BS + off
    counts = np.array([c, 0, rng.randint(1, c + 1), c,
                       min(c, BS - off)])
    wc = rng.randint(0, 2 ** 32, (nb,), dtype=np.uint64).astype(np.uint32)
    shape = (n, b, c, cfg.num_kv_heads, cfg.head_dim)
    dt = jnp.dtype(cfg.dtype)
    updates = tuple(
        {"k_new": jnp.asarray(rng.standard_normal(shape), dt),
         "v_new": jnp.asarray(rng.standard_normal(shape), dt)}
        for _ in cfg.pattern)
    return (tuple(pools), updates, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(counts, jnp.int32),
            jnp.asarray(wc))


@pytest.mark.parametrize("off", [0, BS // 2, BS - 1])
@pytest.mark.parametrize("c", [1, 5, 16, 32])
@pytest.mark.parametrize("mode", ["plain", "sealed", "sealed+mac"])
def test_append_tokens_bitwise_word_roll(mode, c, off):
    cfg = get_reduced("internlm2_1_8b")
    seal = (None if mode == "plain" else
            SS.cache_seal_config(bytes(range(32)),
                                 verify=mode == "sealed+mac"))
    rng = np.random.RandomState(1000 * c + off)
    args = _case(cfg, c, off, rng)
    new = jax.jit(lambda *a: PG.append_tokens(cfg, seal, *a))(*args)
    old = jax.jit(lambda *a: _append_word_roll(cfg, seal, *a))(*args)
    (new_pools, new_wc), (old_pools, old_wc) = new, old
    np.testing.assert_array_equal(np.asarray(new_wc), np.asarray(old_wc))
    for pn, po in zip(new_pools, old_pools):
        for key in ("k", "v", "mac_k", "mac_v"):
            np.testing.assert_array_equal(np.asarray(pn[key]),
                                          np.asarray(po[key]), err_msg=key)
    # the append did write: row 1 bumped nothing, the others did
    pools, _, tables, lengths, counts, wc = args
    bumped = np.asarray(new_wc) != np.asarray(wc)
    first = np.asarray(tables)[np.arange(5), np.asarray(lengths) // BS]
    assert list(bumped[first]) == [True, False, True, True, True]
    assert not np.array_equal(np.asarray(new_pools[0]["k"]),
                              np.asarray(pools[0]["k"]))
