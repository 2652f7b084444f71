"""The serving path's Pallas kernels compile for a TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with JAX, compiles each
kernel for a v5e chip that is described through
``jax.experimental.topologies`` and not attached. This catches what
interpret mode cannot (block shapes Mosaic refuses, relayouts it cannot
lower, scalars outside SMEM) at the widths of internlm2_1_8b and of
Moonlight's chip share, keeps an element-granular gather out of the
cache's write path, and keeps the plaintext of the held experts out of the
optimised program of a MoE layer's decode. The topology
is described in a fixture, never at import, so every pytest worker collects
the same tests and only the one that runs this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import SealConfig
from repro.configs import get_config
from repro.core import coloe as CL
from repro.core import plan as P
from repro.core import sealed_store as SS
from repro.core.sealed_tensor import SealedTensor, SealMeta
from repro.kernels import chacha20 as CC
from repro.kernels import flash_attention as FA
from repro.kernels import ops
from repro.kernels import sealed_gmm as SG
from repro.kernels import sealed_matmul as SM
from repro.models import cache as MC
from repro.models import layers as L
from repro.models import paged as PG
from repro.models import transformer as T


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("k,n", [(2048, 8192), (8192, 2048), (2048, 92544)])
def test_sealed_matmul_compiles_for_v5e(one_chip, m, k, n):
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(x, w, mask, key, nonce, wc):
        return SM.sealed_matmul(x, w, mask, key, nonce, wc, bm=min(m, 128),
                                bk=128, bn=128, interpret=False,
                                compute_dtype="bfloat16")

    _compile(fn, S((m, k), jnp.float32), S((k, n), jnp.uint32),
             S((k,), jnp.bool_), S((8,), jnp.uint32), S((3,), jnp.uint32),
             S((1,), jnp.uint32))


def test_chacha20_keystream_compiles_for_v5e(one_chip):
    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)

    def fn(key, nonce, ctr):
        return CC.chacha20_keystream(key, nonce, ctr, tile=1024,
                                     interpret=False)

    _compile(fn, S((8,)), S((3,)), S((65536,)))


def test_flash_attention_compiles_for_v5e(one_chip):
    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fn(q, k, v):
        return FA.flash_attention(q, k, v, scale=128 ** -0.5,
                                  interpret=False)

    _compile(fn, S((1, 2048, 16, 128)), S((1, 2048, 8, 128)),
             S((1, 2048, 8, 128)))


_GATHER = re.compile(
    r"= \w+\[([\d,]*)\]\S* gather\(.*slice_sizes=\{([\d,]*)\}")


def _word_gathers(text, min_elems):
    """Output shapes of the gathers in an HLO text that move one element per
    index (all-one ``slice_sizes``) into an output of >= min_elems."""
    out = []
    for shape, sizes in _GATHER.findall(text):
        elems = 1
        for d in filter(None, shape.split(",")):
            elems *= int(d)
        if set(sizes.split(",")) == {"1"} and elems >= min_elems:
            out.append(shape)
    return out


@pytest.mark.parametrize("seal", ["plain", "sealed+mac"])
@pytest.mark.parametrize("c,b", [(1, 32), (32, 8)])     # decode tick, chunk
def test_append_tokens_has_no_word_gather_on_v5e(one_chip, seal, c, b):
    """internlm2_1_8b's append at the cells' shapes (24 layers, 2049 blocks
    of 16 tokens x 512 words) gathers whole tokens, never single words."""
    cfg = get_config("internlm2_1_8b")
    nb, bs, mb = 2049, 16, 64
    cs = (None if seal == "plain"
          else SS.cache_seal_config(bytes(range(32)), verify=True))

    def S(sd):
        return jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=one_chip)

    pools = jax.tree.map(S, MC.paged_pool_spec(cfg, nb, bs))
    kv = S(jax.ShapeDtypeStruct(
        (cfg.n_superblocks(), b, c, cfg.num_kv_heads, cfg.head_dim),
        jnp.bfloat16))
    updates = tuple({"k_new": kv, "v_new": kv} for _ in cfg.pattern)
    i32 = lambda *shape: S(jax.ShapeDtypeStruct(shape, jnp.int32))
    wc = S(jax.ShapeDtypeStruct((nb,), jnp.uint32))

    def fn(pools, updates, tables, lengths, counts, wc):
        return PG.append_tokens(cfg, cs, pools, updates, tables, lengths,
                                counts, wc)

    text = jax.jit(fn).lower(pools, updates, i32(b, mb), i32(b), i32(b),
                             wc).compile().as_text()
    wpb = bs * MC.kv_words_per_token(cfg)
    assert wpb == 8192
    assert _word_gathers(text, wpb) == []

    # the check finds the word-roll gather it guards against
    def word_roll(x, idx):
        return jnp.take_along_axis(x, idx, axis=-1)

    text = jax.jit(word_roll).lower(
        S(jax.ShapeDtypeStruct((b, 2 * wpb), jnp.uint32)),
        i32(b, 2 * wpb)).compile().as_text()
    assert _word_gathers(text, wpb)


def _internlm2_cache(seal, sharding, nb=2049, bs=16):
    cfg = get_config("internlm2_1_8b")
    cs = (None if seal == "plain"
          else SS.cache_seal_config(bytes(range(32)), verify=True))
    pools = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=sharding),
        MC.paged_pool_spec(cfg, nb, bs))
    return cfg, cs, pools


@pytest.mark.parametrize("seal", ["plain", "sealed+mac"])
@pytest.mark.parametrize("b,c", [(32, 1), (8, 32)])     # decode tick, chunk
def test_sealed_paged_attention_compiles_for_v5e(one_chip, seal, b, c):
    """internlm2_1_8b's cache read at the cells' shapes: 16 query heads over
    8 kv heads of 128, 16-token blocks, 64-block tables over a stacked pool
    of 24 layers x 2049 blocks; 32 rows of one query (the tick) and 8 rows
    of a 32-token chunk."""
    cfg, cs, pools = _internlm2_cache(seal, one_chip)
    pool = pools[0]
    mb = 64

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(q, k, v, tables, lengths, meta, wcb, expect, hk):
        sealed = cs is not None
        return ops.paged_attention(
            q, k, v, tables, lengths, meta, block_size=16,
            scale=128 ** -0.5, wcb=wcb if sealed else None,
            expect=expect if sealed else None,
            hash_keys=hk if sealed else None, interpret=False)

    _compile(fn, S((b, c, 16, 128), jnp.bfloat16), pool["k"], pool["v"],
             S((b, mb), jnp.int32), S((b,), jnp.int32), S((16,), jnp.int32),
             S((b, mb), jnp.uint32), S((2, b, mb), jnp.uint32),
             S((2, 64, 128), jnp.uint32))


_VIEW = re.compile(r"bf16\[32,1024,8,128\]")
_POOL_SLICE = re.compile(r"u32\[(?:1,)?2049,(?:8192|64,128)\]")


def test_decode_tick_builds_no_dense_view_on_v5e(one_chip, monkeypatch):
    """internlm2_1_8b's sealed, verified decode logits at the tick's shapes
    (32 slots of 64 blocks): the optimised program holds no dense cache
    view and no per-layer slice of the pool, which the kernel reads in
    place. The check finds both in the dense view of one layer."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg, cs, pools = _internlm2_cache("sealed+mac", one_chip)
    b, mb = 32, 64

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(lambda sd: S(sd.shape, jnp.bfloat16),
                          T.param_spec(cfg))
    i32 = lambda *shape: S(shape, jnp.int32)
    wc = S((2049,), jnp.uint32)

    def tick(params, pools, tables, lengths, wc, tokens, live):
        return PG.decode_logits(cfg, params, pools, tables, lengths, wc,
                                tokens, cs, live=live)

    text = jax.jit(tick).lower(params, pools, i32(b, mb), i32(b), wc,
                               i32(b, 1), S((b, 1), jnp.bool_)
                               ).compile().as_text()
    assert "sealed_paged_attention" in text
    assert _VIEW.findall(text) == [] and _POOL_SLICE.findall(text) == []

    def view(pools, tables, lengths, wc):
        pool = jax.tree.map(lambda a: a[3], pools[0])
        return PG._dense_view(cfg, cs, pool, tables, lengths, wc)

    text = jax.jit(view).lower(pools, i32(b, mb), i32(b), wc
                               ).compile().as_text()
    assert _VIEW.findall(text) and _POOL_SLICE.findall(text)


@pytest.mark.parametrize("t,k,n", [(32, 2048, 1408), (32, 1408, 2048),
                                   (256, 2048, 1408)])
def test_sealed_gmm_compiles_for_v5e(one_chip, t, k, n):
    """Moonlight's held experts (8 x 2048 x 1408): a decode tick's 32 tokens
    and a chunk step's 256."""
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(x, w, mask, key, nonce, wc):
        return SG.sealed_gmm(x, w, mask, key, nonce, wc, bk=128, bn=128,
                             interpret=False, compute_dtype="bfloat16")

    _compile(fn, S((8, t, k), jnp.bfloat16), S((8, k, n), jnp.uint32),
             S((8, k), jnp.bool_), S((8,), jnp.uint32), S((3,), jnp.uint32),
             S((8,), jnp.uint32))


def _spec_sealed(tree, seal, sharding):
    """The sealed image ``seal_params`` makes of ``tree``, as shapes: each
    leaf in the layout ``tile_geometry`` gives it (tiles, or ColoE's line
    records where it gives none), for a compile against a described chip.
    Returns the materialisation the serving graph runs on it."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    tensors = {}
    for kp, leaf in flat:
        pt = P._path_tuple(kp)
        g = SS.tile_geometry(pt, leaf.shape, leaf.dtype, seal)
        if g is None:
            words = int(np.prod(leaf.shape))
            meta = SealMeta(scheme="coloe", layout="lines", dtype="float32",
                            nonce=(1, 2), shape=tuple(leaf.shape),
                            orig_len=words)
            st = SealedTensor(S((-(-words // CL.WORDS_PER_LINE),
                                 CL.COLOE_LINE_WORDS), jnp.uint32),
                              None, None, None, None, meta)
        else:
            lead = leaf.shape[:g.n_batch]
            meta = SealMeta(scheme="coloe", layout="tiles", dtype="float32",
                            nonce=(3, 5, 7), shape=tuple(leaf.shape),
                            n_batch=g.n_batch, k_ndim=g.k_ndim,
                            n_out=g.n_out, bk=g.bk, bn=g.bn, fused=g.fused)
            st = SealedTensor(S(leaf.shape, jnp.uint32), None,
                              S(lead + (g.k,), jnp.bool_),
                              S(lead + (8,), jnp.uint32),
                              S(lead, jnp.uint32), meta)
        tensors["/".join(pt)] = st
    plans = dict.fromkeys(tensors)
    return tensors, lambda t: SS.fused_params(
        SS.SealedParams(t, plans, treedef, seal), bytes(range(32)))


_PLAIN_EXPERTS = re.compile(r"(?:f32|bf16)\[(?:1,)?8,(?:2048,1408|1408,2048)\]")


def test_moe_layer_decode_keeps_the_experts_sealed_on_v5e(one_chip,
                                                          monkeypatch):
    """One Moonlight MoE layer's decode at the chip share's widths (d 2048,
    8 held experts of width 1408, 32 tokens), its weights as the sealed
    store holds them: the held experts reach ``sealed_gmm`` still sealed,
    so the optimised program holds no plaintext (8, 2048, 1408) array."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = get_config("moonlight_16b_a3b_ep8")
    seal = SealConfig(mode="coloe", smart_ratio=0.5, verify=True)
    layer = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a[None], L.init_moe_held(cfg, jax.random.key(0))))
    tensors, materialize = _spec_sealed({"mlp": layer}, seal, one_chip)
    x = jax.ShapeDtypeStruct((32, 1, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)

    def fn(t, x):
        def body(h, p):
            out, _ = L.moe_held(cfg, p["mlp"], h)
            return out, None
        return jax.lax.scan(body, x, materialize(t))[0]

    text = jax.jit(fn).lower(tensors, x).compile().as_text()
    assert "tpu_custom_call" in text
    assert _PLAIN_EXPERTS.findall(text) == []
