"""Decode-time state: KV caches (global + sliding-window ring buffers),
RG-LRU recurrent state, SSD state, causal-conv tails — plus the host-side
block allocator and copy-on-write prefix registry behind the paged pools.

All caches are plain pytrees of arrays so they pass through jit/pjit/scan.
Invalid KV slots carry position 2**30 so the causal mask hides them.

Two cache families live here:

* the **contiguous** per-request caches (``model_cache_*``) used by
  ``transformer.prefill/decode_step`` — one (batch, cache_len, ...) buffer
  per attention layer;
* the **paged block pools** (``paged_pool_*``) used by the continuous
  serving path (``models/paged.py``): a shared pool of fixed-size blocks
  stored as raw u32 words, indexed per request through a block table.
  Storing words (not floats) makes the pool seal-agnostic — the sealed and
  plaintext paths share every byte of layout, so their token streams are
  bit-identical by construction. Block 0 is reserved as a scratch target
  for inactive slots.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.config import ModelConfig

INVALID_POS = 2**30

SCRATCH_BLOCK = 0      # pool block 0: write target for inactive serve slots


def attn_cache_spec(cfg: ModelConfig, batch: int, cache_len: int, kind: str):
    """ShapeDtypeStructs for one attention layer's cache."""
    if kind == "local_attn" and cfg.window:
        cache_len = min(cache_len, cfg.window)
    dt = jnp.dtype(cfg.dtype)
    if cfg.mla is not None:
        return {
            "c": jax.ShapeDtypeStruct((batch, cache_len, cfg.mla.latent), dt),
            "pos": jax.ShapeDtypeStruct((cache_len,), jnp.int32),
        }
    return {
        "k": jax.ShapeDtypeStruct((batch, cache_len, cfg.num_kv_heads, cfg.head_dim), dt),
        "v": jax.ShapeDtypeStruct((batch, cache_len, cfg.num_kv_heads, cfg.head_dim), dt),
        "pos": jax.ShapeDtypeStruct((cache_len,), jnp.int32),
    }


def attn_cache_init(cfg: ModelConfig, batch: int, cache_len: int, kind: str):
    spec = attn_cache_spec(cfg, batch, cache_len, kind)
    return {k: (jnp.full(v.shape, INVALID_POS, jnp.int32) if k == "pos"
                else jnp.zeros(v.shape, v.dtype)) for k, v in spec.items()}


def rglru_cache_spec(cfg: ModelConfig, batch: int):
    w = cfg.rglru_block_width or cfg.d_model
    return {
        "h": jax.ShapeDtypeStruct((batch, w), jnp.float32),
        "conv": jax.ShapeDtypeStruct((batch, 3, w), jnp.dtype(cfg.dtype)),
    }


def rglru_cache_init(cfg: ModelConfig, batch: int):
    s = rglru_cache_spec(cfg, batch)
    return jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype), s)


def ssd_cache_spec(cfg: ModelConfig, batch: int):
    di, n = cfg.ssm_d_inner, cfg.ssm_state
    h, p = cfg.ssm_heads, cfg.ssm_head_dim
    return {
        "state": jax.ShapeDtypeStruct((batch, h, p, n), jnp.float32),
        "conv": jax.ShapeDtypeStruct((batch, cfg.ssm_conv - 1, di + 2 * n),
                                     jnp.dtype(cfg.dtype)),
    }


def ssd_cache_init(cfg: ModelConfig, batch: int):
    s = ssd_cache_spec(cfg, batch)
    return jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype), s)


def block_cache_spec(cfg: ModelConfig, kind: str, batch: int, cache_len: int):
    if kind in ("attn", "local_attn"):
        return attn_cache_spec(cfg, batch, cache_len, kind)
    if kind == "rglru":
        return rglru_cache_spec(cfg, batch)
    if kind == "ssd":
        return ssd_cache_spec(cfg, batch)
    raise ValueError(kind)


def block_cache_init(cfg: ModelConfig, kind: str, batch: int, cache_len: int):
    if kind in ("attn", "local_attn"):
        return attn_cache_init(cfg, batch, cache_len, kind)
    if kind == "rglru":
        return rglru_cache_init(cfg, batch)
    if kind == "ssd":
        return ssd_cache_init(cfg, batch)
    raise ValueError(kind)


def _stack_spec(specs):
    return jax.tree.map(
        lambda *xs: jax.ShapeDtypeStruct((len(xs),) + xs[0].shape, xs[0].dtype),
        *specs)


def model_cache_spec(cfg: ModelConfig, batch: int, cache_len: int):
    """Cache pytree spec: tuple over pattern positions of stacked (n_super, ...)."""
    n = cfg.n_superblocks()
    out = []
    for kind in cfg.pattern:
        one = block_cache_spec(cfg, kind, batch, cache_len)
        out.append(_stack_spec([one] * n))
    return tuple(out)


def model_cache_init(cfg: ModelConfig, batch: int, cache_len: int):
    n = cfg.n_superblocks()
    out = []
    for kind in cfg.pattern:
        one = block_cache_init(cfg, kind, batch, cache_len)
        out.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one))
    return tuple(out)


# --------------------------------------------------------------------------
# paged block pools (continuous serving)
# --------------------------------------------------------------------------

def pool_streams(cfg: ModelConfig):
    """The word streams of one layer's paged pool: one latent entry per
    token ("c") for MLA, else K and V."""
    return ("c",) if cfg.mla is not None else ("k", "v")


def token_shape(cfg: ModelConfig):
    """Shape of one token's entry in each stream."""
    if cfg.mla is not None:
        return (cfg.mla.latent,)
    return (cfg.num_kv_heads, cfg.head_dim)


def kv_words_per_token(cfg: ModelConfig) -> int:
    """u32 words one token's entry of one stream (K, V or the latent)
    occupies in a pool block."""
    elems = 1
    for n in token_shape(cfg):
        elems *= n
    nbytes = elems * jnp.dtype(cfg.dtype).itemsize
    assert nbytes % 4 == 0, (token_shape(cfg), cfg.dtype)
    return nbytes // 4


def kv_to_words(x):
    """Bitcast a (..., E) float tensor to (..., E*itemsize//4) u32 words."""
    dt = x.dtype
    if dt.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if dt.itemsize == 2:
        lead, e = x.shape[:-1], x.shape[-1]
        assert e % 2 == 0, x.shape
        h16 = jax.lax.bitcast_convert_type(x, jnp.uint16)
        return jax.lax.bitcast_convert_type(
            h16.reshape(lead + (e // 2, 2)), jnp.uint32)
    raise TypeError(f"unsupported kv dtype {dt}")


def words_to_kv(words, dtype):
    """Inverse of ``kv_to_words``: (..., W) u32 -> (..., E) dtype."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(words, dtype)
    if dtype.itemsize == 2:
        lead, w = words.shape[:-1], words.shape[-1]
        u16 = jax.lax.bitcast_convert_type(words, jnp.uint16)   # (..., W, 2)
        return jax.lax.bitcast_convert_type(u16, dtype).reshape(
            lead + (w * 2,))
    raise TypeError(f"unsupported kv dtype {dtype}")


def block_shape(cfg: ModelConfig, block_size: int):
    """Trailing shape of one stream's pool block. A K or V block is a
    (rows, lanes) stack of u32 tiles, a token a whole number of rows and
    every row whole kv heads: the paged attention kernel DMAs it whole out
    of the stacked pool (``kernels/sealed_paged_attention.py``). MLA's
    latent blocks, which ``paged._dense_view`` gathers, stay flat."""
    wpt = kv_words_per_token(cfg)
    if cfg.mla is not None:
        return (block_size * wpt,)
    lanes = math.gcd(wpt, 128)
    return (block_size * wpt // lanes, lanes)


def paged_pool_spec(cfg: ModelConfig, num_blocks: int, block_size: int):
    """ShapeDtypeStructs of the paged pools: a tuple over pattern positions
    of {"k", "v": (n_super, num_blocks, *block_shape) u32, "mac_k",
    "mac_v": (n_super, num_blocks) u32, "lid": (n_super,) u32} — for MLA
    one latent stream {"c", "mac_c"} in place of K and V. ``lid`` is
    the globally unique layer id folded into the block keystream (nonce
    word 0). ``mac_*`` are the co-located per-block Carter–Wegman
    tags (one word per stream — 0.1% of a block); they are always allocated
    so the pool pytree structure is seal-agnostic, and stay zero unless the
    cache seal carries a MAC context."""
    n = cfg.n_superblocks()
    blk = block_shape(cfg, block_size)
    streams = pool_streams(cfg)
    out = []
    for kind in cfg.pattern:
        assert kind in ("attn", "local_attn"), \
            f"paged pools cover attention layers only (got {kind!r})"
        one = {s: jax.ShapeDtypeStruct((n, num_blocks) + blk, jnp.uint32)
               for s in streams}
        one.update({f"mac_{s}": jax.ShapeDtypeStruct((n, num_blocks),
                                                     jnp.uint32)
                    for s in streams})
        one["lid"] = jax.ShapeDtypeStruct((n,), jnp.uint32)
        out.append(one)
    return tuple(out)


# --------------------------------------------------------------------------
# host-side block accounting: refcounted allocator + prefix registry
# --------------------------------------------------------------------------

class BlockAllocator:
    """Refcounted free-list allocator over pool blocks 1..num_blocks-1
    (block 0 is the reserved scratch target).

    Shared prefix blocks are referenced by several slots (and by the
    ``PrefixRegistry``) at once; a block returns to the free list only when
    its last reader drops it. Counter-mode sealing makes multi-reader
    blocks free: the OTP derives from the pool address + write counter, so
    N tables can unseal the same ciphertext block with zero re-encryption.
    """

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))   # pop() -> low ids
        self.refcount = [0] * num_blocks

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, n: int):
        """Allocate n blocks at refcount 1; returns None if short."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self.refcount[b] = 1
        return out

    def incref(self, blocks):
        for b in blocks:
            assert self.refcount[b] > 0, f"incref of free block {b}"
            self.refcount[b] += 1

    def decref(self, blocks):
        """Drop one reference per block; frees blocks reaching zero."""
        freed = []
        for b in blocks:
            assert self.refcount[b] > 0, f"decref of free block {b}"
            self.refcount[b] -= 1
            if self.refcount[b] == 0:
                self._free.append(b)
                freed.append(b)
        return freed


class PrefixRegistry:
    """Prefix-hash -> block map for copy-on-write prefix sharing.

    Full blocks are keyed by a chain hash over their token contents (key_i
    depends on every token in blocks [0, i]), so a lookup walks the prompt
    block-by-block and stops at the first miss — identical prefixes map to
    identical chains regardless of which request produced them. A *partial*
    entry additionally records the committed token tail living at the start
    of a block that is not yet full (the prompt tail of the donor); a match
    against it shares those tokens too, and the sharer copy-on-writes the
    block before appending into it (``serve/engine.py``).

    The registry holds one reference per registered block; ``evict_lru``
    releases least-recently-used chains back to the allocator when
    admission runs short of free blocks.
    """

    def __init__(self, alloc: BlockAllocator, block_size: int):
        self.alloc = alloc
        self.bs = block_size
        self._full = {}       # chain_key -> block id
        self._partial = {}    # chain_key of parent -> (block id, token tuple)
        self._parent = {}     # chain_key -> parent chain_key (purge cascade)
        self._lru = {}        # chain_key -> last-use tick (full entries)
        self._tick = 0
        self.hits = 0         # blocks served from the registry

    @staticmethod
    def chain_key(parent, block_tokens) -> int:
        return hash((parent, tuple(int(t) for t in block_tokens)))

    def match(self, prompt):
        """Longest shared prefix for ``prompt``.

        Returns (full_blocks, partial, n_shared): ``full_blocks`` are
        registered block ids covering prompt[:len(full_blocks)*bs],
        ``partial`` is an optional (block_id, n_tokens) extending the chain
        mid-block, and ``n_shared`` the total shared token count. At least
        one prompt token is always left to recompute (its logits seed the
        first sampled token), so n_shared <= len(prompt) - 1.
        """
        bs, plen = self.bs, len(prompt)
        self._tick += 1
        full, key = [], None
        while (len(full) + 1) * bs <= plen - 1:
            i = len(full)
            k = self.chain_key(key, prompt[i * bs:(i + 1) * bs])
            b = self._full.get(k)
            if b is None:
                break
            key = k
            full.append(b)
            self._lru[key] = self._tick
        n_shared = len(full) * bs
        partial = None
        ent = self._partial.get(key)
        if ent is not None:
            b, toks = ent
            j = 0
            while (j < len(toks) and n_shared + j < plen - 1
                   and int(prompt[n_shared + j]) == toks[j]):
                j += 1
            if j > 0:
                partial = (b, j)
                n_shared += j
        self.hits += len(full) + (1 if partial else 0)
        return full, partial, n_shared

    def register(self, prompt, blocks):
        """Record a freshly prefilled prompt: ``blocks`` is the slot's
        table prefix covering the prompt. Newly registered blocks gain a
        registry reference; chains already present are left untouched."""
        bs, plen = self.bs, len(prompt)
        key = None
        for i in range(plen // bs):
            k = self.chain_key(key, prompt[i * bs:(i + 1) * bs])
            if k not in self._full:
                self._full[k] = blocks[i]
                self.alloc.incref([blocks[i]])
                self._parent[k] = key
            key = k
            self._lru[key] = self._tick
        tail = tuple(int(t) for t in prompt[(plen // bs) * bs:])
        if tail and key not in self._partial:
            b = blocks[plen // bs]
            self._partial[key] = (b, tail)
            self.alloc.incref([b])

    def purge_blocks(self, blocks) -> int:
        """Forget every chain that touches ``blocks`` (untrusted content —
        e.g. a failed integrity check) plus all descendant chains: a chain
        hash commits to the *token* contents of blocks [0, i], so any chain
        running through a purged block would keep serving the pre-tamper
        tokens to future matches. Drops the registry's references; returns
        the number of blocks actually freed."""
        bad = {int(b) for b in blocks}
        dead = {k for k, b in self._full.items() if b in bad}
        # cascade down the parent links until closed
        changed = True
        while changed:
            changed = False
            for k, parent in self._parent.items():
                if parent in dead and k in self._full and k not in dead:
                    dead.add(k)
                    changed = True
        release = []
        for k in dead:
            release.append(self._full.pop(k))
            self._lru.pop(k, None)
            self._parent.pop(k, None)
        for k in list(self._partial):
            b, _ = self._partial[k]
            if b in bad or k in dead:
                release.append(self._partial.pop(k)[0])
        return len(self.alloc.decref(release))

    def evict_lru(self, need_free: int) -> int:
        """Release LRU chains until the allocator has ``need_free`` free
        blocks (or nothing evictable remains). Only releases blocks whose
        sole reference is the registry's — blocks shared by live slots
        stay put. Returns the number of blocks freed."""
        freed = 0
        for key in sorted(self._lru, key=self._lru.get):
            if self.alloc.free_count >= need_free:
                break
            blocks = []
            if key in self._full and self.alloc.refcount[self._full[key]] == 1:
                blocks.append(self._full.pop(key))
                self._lru.pop(key)
            ent = self._partial.get(key)
            if ent and self.alloc.refcount[ent[0]] == 1:
                blocks.append(self._partial.pop(key)[0])
            freed += len(self.alloc.decref(blocks))
        # drop partial entries whose parent chain is gone
        dead = [k for k in self._partial
                if k is not None and k not in self._full]
        for k in dead:
            if self.alloc.free_count >= need_free:
                break
            if self.alloc.refcount[self._partial[k][0]] == 1:
                freed += len(self.alloc.decref([self._partial.pop(k)[0]]))
        return freed


def paged_pool_init(cfg: ModelConfig, num_blocks: int, block_size: int):
    spec = paged_pool_spec(cfg, num_blocks, block_size)
    n, npat = cfg.n_superblocks(), len(cfg.pattern)
    out = []
    for j, sj in enumerate(spec):
        one = {k: jnp.zeros(v.shape, jnp.uint32) for k, v in sj.items()
               if k != "lid"}
        one["lid"] = (jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(npat)
                      + jnp.uint32(j))
        out.append(one)
    return tuple(out)
