"""The sealed paged attention kernel against the dense view it replaces.

Interpret mode, reduced shapes: one attention layer of the reduced
internlm2 (4 query heads of 16), a pool of 4-token blocks. Short tables
(4 blocks, one group of the kernel's 8) hold rows of lengths 0, 1, 4 (a
block boundary) and the full 16 in the decode tick — 13 in the chunk step,
whose three fresh tokens must fit the view. Long tables (20 blocks, three
groups) hold lengths that end inside the second group, inside the third
and at the table's end, and in the chunk step one that ends exactly at a
group boundary: they run the double buffer's slot swap, the prefetch of the
next group and the softmax rescale across groups. Unused table entries
point at block 0. The kernel plus the fresh-token merge
(``blocks.attn_block_sub_apply`` with ``attend``) must give what the dense
view plus ``attention_apply`` gives, in f32 to 2e-5: the two sum the same
terms in another order (online softmax over 8-block groups, the fresh keys
merged last). The integrity verdict flips for exactly the row that owns a
tampered resident block, in the first group or in a later one.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import sealed_store as SS
from repro.kernels import ref as KR
from repro.models import blocks as B
from repro.models import cache as MC
from repro.models import layers as L
from repro.models import paged as PG

BS, C = 4, 3
LAYER = 1                           # read in place from the stacked pool
CHUNK_FRESH = (3, 1, 2, 3)
# table blocks: lengths in the decode tick, lengths in the chunk step
TABLES = {"short": (4, (0, 1, 4, 16), (0, 1, 4, 13)),
          "long": (20, (0, 37, 70, 80), (0, 33, 64, 77))}


@functools.lru_cache(maxsize=None)
def _setup(kv_heads, sealed, dtype="float32", softcap=0.0, table="short"):
    cfg = get_reduced("internlm2_1_8b").with_(dtype=dtype,
                                              num_kv_heads=kv_heads,
                                              attn_softcap=softcap)
    mb = TABLES[table][0]
    b = len(CHUNK_FRESH)
    nb = 1 + b * mb
    rng = np.random.RandomState(kv_heads)
    n = cfg.n_superblocks()
    kv = [jnp.asarray(rng.randn(n, b, mb * BS, kv_heads, cfg.head_dim),
                      cfg.dtype) for _ in range(2)]
    own = 1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    wc = jnp.asarray(rng.randint(1, 5, nb), jnp.uint32)
    seal = (SS.cache_seal_config(bytes(range(32)), verify=True) if sealed
            else None)
    pools = jax.jit(lambda *a: PG.prefill_write(cfg, seal, *a))(
        MC.paged_pool_init(cfg, nb, BS), ({"k": kv[0], "v": kv[1]},),
        jnp.asarray(own), wc)
    p = L.init_attention(cfg, jax.random.key(kv_heads))
    return cfg, seal, pools, own, wc, p


def _tables(own, lengths):
    nblk = -(-np.asarray(lengths) // BS)
    return jnp.asarray(np.where(np.arange(own.shape[1])[None]
                                < nblk[:, None], own, 0))


def _per_layer(pool):
    return {"layer": jnp.int32(LAYER), "lid": pool["lid"][LAYER],
            "mac_k": pool["mac_k"][LAYER], "mac_v": pool["mac_v"][LAYER]}


def _inputs(cfg, mode, own):
    table = next(k for k, v in TABLES.items() if v[0] == own.shape[1])
    lens = TABLES[table][1 if mode == "decode" else 2]
    b, q = len(lens), (1 if mode == "decode" else C)
    lengths = jnp.asarray(lens, jnp.int32)
    fresh = (jnp.ones((b,), jnp.int32) if mode == "decode"
             else jnp.asarray(CHUNK_FRESH, jnp.int32))
    positions = lengths[:, None] + jnp.arange(q, dtype=jnp.int32)[None]
    h = jax.random.normal(jax.random.key(7), (b, q, cfg.d_model), cfg.dtype)
    return lengths, fresh, positions, h


def _kernel(cfg, seal, pools, own, wc, p, mode):
    """The layer's output through the kernel, and its verdict per row."""
    lengths, fresh, positions, h = _inputs(cfg, mode, own)
    pool = pools[0]
    attend = PG._pool_attention(cfg, seal, pool, _per_layer(pool),
                                _tables(own, lengths), lengths, wc, fresh)
    out, up = B.attn_block_sub_apply(cfg, "attn", p, h, positions, mode,
                                     {"attend": attend, "cl": fresh})
    return out, np.asarray(up["ok"])


def _view(cfg, seal, pools, own, wc, p, mode):
    """The layer's output through the dense view."""
    lengths, fresh, positions, h = _inputs(cfg, mode, own)

    @jax.jit
    def dense(pool, wc):
        view, _ = PG._dense_view(
            cfg, seal, jax.tree.map(lambda a: a[LAYER], pool),
            _tables(own, lengths), lengths, wc,
            pos_len=None if mode == "decode" else lengths + fresh)
        view["cl"] = fresh
        return B.attn_block_sub_apply(cfg, "attn", p, h, positions, mode,
                                      view)[0]

    return dense(pools[0], wc)


# every mode, seal, head layout and table length appears; bf16 takes the
# packed (even/odd) path, at bf16's tolerance: the view rounds normalised
# probabilities to bf16, the kernel unnormalised ones; a logit softcap
# small enough to bend the scores (gemma-2 caps its attention logits)
@pytest.mark.parametrize("mode,sealed,kv_heads,dtype,softcap,table,tol", [
    ("decode", True, 2, "float32", 0.0, "short", 2e-5),
    ("decode", False, 4, "float32", 0.0, "short", 2e-5),
    ("chunk", True, 4, "float32", 0.0, "short", 2e-5),
    ("chunk", False, 2, "float32", 2.0, "short", 2e-5),
    ("decode", True, 2, "bfloat16", 0.0, "short", 3e-2),
    ("decode", True, 2, "float32", 0.0, "long", 2e-5),
    ("chunk", True, 2, "float32", 0.0, "long", 2e-5),
])
def test_kernel_matches_dense_view(mode, sealed, kv_heads, dtype, softcap,
                                   table, tol):
    args = _setup(kv_heads, sealed, dtype, softcap, table) + (mode,)
    got, ok = _kernel(*args)
    want = _view(*args)
    fresh = np.asarray(_inputs(args[0], mode, args[3])[1])
    real = np.arange(got.shape[1])[None, :] < fresh[:, None]   # real queries
    np.testing.assert_allclose(np.asarray(got, np.float32)[real],
                               np.asarray(want, np.float32)[real],
                               rtol=tol, atol=tol)
    assert ok.all()


def _tamper(pools, wc, what, block):
    pj = dict(pools[0])
    if what == "word":
        pj["k"] = pj["k"].at[LAYER, block, 0, 3].set(
            pj["k"][LAYER, block, 0, 3] ^ jnp.uint32(1 << 9))
    elif what == "tag":
        pj["mac_v"] = pj["mac_v"].at[LAYER, block].set(
            pj["mac_v"][LAYER, block] ^ jnp.uint32(1))
    else:
        wc = wc.at[block].add(jnp.uint32(1))
    return (pj,), wc


# (row, table entry) of a flipped resident block -> the verdict per row
FLIPS = {"short": {(3, 3): [True, True, True, False]},
         "long": {(2, 16): [True, True, False, True],
                  (2, 17): [True, True, False, True],
                  (3, 19): [True, True, True, False]}}


@pytest.mark.parametrize("what", ["word", "tag", "counter"])
def test_tamper_fails_only_its_row(what):
    """A one-bit flip in a resident block, in its tag or in its write
    counter fails the owning row alone: in the short tables row 3's last
    block (positions 12..15); in the long tables blocks of the third group
    of 8 — row 2's first of that group and its last resident one
    (positions 68..71 of its 70 tokens), and row 3's block 19, the last of
    its full table. The same flip in block 0, which every unused table
    entry points at, fails nothing."""
    for table, flips in FLIPS.items():
        cfg, seal, pools, own, wc, p = _setup(2, True, table=table)
        for (row, entry), want in flips.items():
            bad_pools, bad_wc = _tamper(pools, wc, what, int(own[row, entry]))
            ok = _kernel(cfg, seal, bad_pools, own, bad_wc, p, "decode")[1]
            assert list(ok) == want, (table, row, entry)
        bad_pools, bad_wc = _tamper(pools, wc, what, 0)
        assert _kernel(cfg, seal, bad_pools, own, bad_wc, p, "decode")[1].all()


def test_plane_order_is_the_block_order_transposed():
    """K/V blocks are sealed in plane order (word w of ChaCha block c pads
    element w * cpb + c) from the same (key, nonce, counter) triples as
    MLA's block order (element c * 16 + w): only the order of words
    within a block's pad differs."""
    key = jnp.arange(8, dtype=jnp.uint32)
    args = (key, (5, 6, 7), jnp.asarray([3, 9]), jnp.asarray([1, 2]), 4, 256)
    planes = np.asarray(KR.cache_block_otp(*args))
    blocks = np.asarray(KR.cache_block_otp(*args, planes=False))
    np.testing.assert_array_equal(
        planes, blocks.reshape(2, 16, 16).transpose(0, 2, 1).reshape(2, 256))
