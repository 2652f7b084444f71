"""The serving path's Pallas kernels compile for a TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with JAX, compiles each
kernel for a v5e chip that is described through
``jax.experimental.topologies`` and not attached. This catches what
interpret mode cannot (block shapes Mosaic refuses, relayouts it cannot
lower, scalars outside SMEM) at the widths of internlm2_1_8b, and keeps an
element-granular gather out of the cache's write path. The topology
is described in a fixture, never at import, so every pytest worker collects
the same tests and only the one that runs this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import sealed_store as SS
from repro.kernels import chacha20 as CC
from repro.kernels import flash_attention as FA
from repro.kernels import sealed_matmul as SM
from repro.models import cache as MC
from repro.models import paged as PG


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
