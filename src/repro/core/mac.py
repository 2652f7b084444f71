"""Truncated Carter–Wegman MACs over the sealed memory image.

Counter-mode sealing (engines, tile weights, paged cache blocks) buys
confidentiality but zero integrity: under CTR a flipped ciphertext bit flips
exactly that plaintext bit, and a replayed (ciphertext, counter) pair
decrypts to the stale plaintext. This module adds the integrity half —
GuardNN / Seculator pair their memory encryption with exactly this kind of
per-line MAC + version check.

Construction (one u32 tag per protected unit — 128 B line, weight tile, or
cache block):

  tag = uhash_r(ciphertext words)  XOR  pad(key, address, write_counter)

* ``uhash`` is a multilinear universal hash over GF(p), p = 2^31 - 1: the
  message is split into 16-bit halves m_i and hashed as sum(r_i * m_i) mod p
  with per-position keys r_i derived once from the sealing key via ChaCha20.
  Working mod the Mersenne prime keeps every intermediate inside u32
  arithmetic (the accelerator has no u64), and two messages collide under a
  random key with probability <= 2^-31.
* ``pad`` is word 0 of one ChaCha20 block keyed by the MAC key with the
  protected unit's (address, write counter, layer/tensor id) folded into the
  counter/nonce — the Wegman-Carter encryption of the hash. Binding the pad
  to the *address* catches block relocation/swaps; binding it to the *write
  counter* catches replay of stale images and counter rollback, because the
  verifier derives the pad from the trusted counter while the stored tag was
  made under the counter value current at write time.

Tags are stored co-located with the payload's counter metadata (a ``macs``
leaf on ``SealedTensor``, ``mac_k``/``mac_v`` words in the paged pools — the
ColoE spirit: verification adds no extra memory stream). Verification is
in-graph and constant-time: every unseal site recomputes the tag and reduces
to a boolean the host checks after the dispatch.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cipher as C

P31 = 0x7FFFFFFF          # 2^31 - 1, Mersenne prime — the hash field
MAX_WORDS = 32768         # per-tag message cap (sum-splitting overflow bound)


class SealedIntegrityError(RuntimeError):
    """A MAC check failed at an unseal site.

    scope: "weights" (fail-stop — the model image is untrusted) or "cache"
    (recoverable — the serve engine fails and retries the owning request).
    ``slots`` / ``rids`` carry the affected serve slots / request ids when
    the failure is attributable.
    """

    def __init__(self, scope: str, detail: str = "",
                 slots: Sequence[int] = (), rids: Sequence[int] = ()):
        self.scope = scope
        self.slots = tuple(int(s) for s in slots)
        self.rids = tuple(int(r) for r in rids)
        msg = f"sealed-memory integrity failure [{scope}]"
        if detail:
            msg += f": {detail}"
        if self.slots:
            msg += f" (slots {list(self.slots)})"
        super().__init__(msg)


# --------------------------------------------------------------------------
# GF(2^31 - 1) arithmetic in pure u32 ops
# --------------------------------------------------------------------------

def _fold(x):
    """Reduce u32 x (any value) to the canonical range [0, P31)."""
    x = (x >> 31) + (x & jnp.uint32(P31))
    x = (x >> 31) + (x & jnp.uint32(P31))          # <= 2^31 -> <= P31
    return jnp.where(x >= P31, x - jnp.uint32(P31), x)


def _mul_mod(a, b):
    """a * b mod P31 for a in [0, P31), b < 2^16 — no wider intermediates.

    Split a = ah*2^16 + al: ah*b < 2^31 and al*b < 2^32 both fit u32, and
    hi*2^16 mod p rewrites (Mersenne: 2^31 ≡ 1) as (hi>>15) + (hi&0x7FFF)<<16.
    """
    ah, al = a >> 16, a & jnp.uint32(0xFFFF)
    hi = ah * b
    lo = al * b
    hi_m = _fold((hi >> 15) + ((hi & jnp.uint32(0x7FFF)) << 16))
    return _fold(hi_m + _fold(lo))


def uhash(keys, words):
    """Multilinear universal hash over the last axis of u32 ``words``.

    keys: (2*W,) u32 in [0, P31); words: (..., W) u32. Each word contributes
    two 16-bit halves. Returns (...,) u32 tags in [0, P31); two distinct
    messages collide with probability <= 2^-31 over the key draw.
    """
    w = jnp.asarray(words, jnp.uint32)
    nh = 2 * w.shape[-1]
    assert nh <= 2 * MAX_WORDS, f"message too long for one tag: {w.shape}"
    assert keys.shape[-1] == nh, (keys.shape, w.shape)
    halves = jnp.stack([w & jnp.uint32(0xFFFF), w >> 16],
                       axis=-1).reshape(w.shape[:-1] + (nh,))
    terms = _mul_mod(keys, halves)                 # (..., nh) in [0, P31)
    # overflow-safe sum: with nh <= 2^16 halves, the low-16 partial sum stays
    # < 2^32 and the high-15 partial sum stays < 2^31 — both exact in u32
    lo = jnp.sum(terms & jnp.uint32(0xFFFF), axis=-1, dtype=jnp.uint32)
    hi = jnp.sum(terms >> 16, axis=-1, dtype=jnp.uint32)
    return combine_halves(lo, hi)


def combine_halves(lo, hi):
    """The hash from the sums of its terms' low 16 bits (``lo``) and high
    15 bits (``hi``): (hi * 2^16 + lo) mod P31, in u32 ops."""
    hi = _fold(hi)
    hi_m = _fold((hi >> 15) + ((hi & jnp.uint32(0x7FFF)) << 16))
    return _fold(hi_m + _fold(lo))


_HK_NONCE = (0x4D414331, 0x68616C66, 0x6B657973)   # "MAC1"/"half"/"keys"


@functools.lru_cache(maxsize=128)
def _hash_keys_host(key_bytes: bytes, n_halves: int) -> np.ndarray:
    """Per-position hash keys r_i in [1, P31), derived once per sealing key
    from a dedicated ChaCha20 nonce domain. Host-side and memoized, so the
    keys enter jitted graphs as constants (``ensure_compile_time_eval``
    keeps the derivation concrete even when first touched inside a trace)."""
    with jax.ensure_compile_time_eval():
        ks = np.asarray(C.chacha20_keystream_u32(
            jnp.asarray(C.key_to_words(key_bytes[:32])), n_halves,
            jnp.asarray(_HK_NONCE, jnp.uint32)))
    k = (ks >> 31) + (ks & np.uint32(P31))
    k = np.where(k >= P31, k - np.uint32(P31), k)
    # a zero key would leave its 16-bit position unauthenticated for the
    # lifetime of the sealing key — exclude it
    return np.where(k == 0, np.uint32(1), k).astype(np.uint32)


def mac_pads(key_words, nonce3, addrs, wcs, lids=0):
    """One u32 Wegman-Carter pad per (address, write counter, id) triple:
    word 0 of ChaCha20(key, counter=addr, nonce=(n0^lid, n1^wc, n2)).
    ``addrs``/``wcs``/``lids`` broadcast together; returns their common
    shape."""
    a = jnp.asarray(addrs, jnp.uint32)
    w = jnp.asarray(wcs, jnp.uint32)
    l = jnp.asarray(lids, jnp.uint32)
    shape = jnp.broadcast_shapes(a.shape, w.shape, l.shape)
    if shape == ():
        shape = (1,)
    a, w, l = (jnp.broadcast_to(t, shape).reshape(-1) for t in (a, w, l))
    nonces = (jnp.uint32(nonce3[0]) ^ l, jnp.uint32(nonce3[1]) ^ w,
              nonce3[2])
    pads = C.chacha20_block(jnp.asarray(key_words, jnp.uint32), a, nonces)
    return pads[:, 0].reshape(shape)


@dataclasses.dataclass(frozen=True)
class MacContext:
    """Static MAC context: the sealing key (hash keys memoize off its bytes)
    plus the pad-domain base nonce. Per-tensor / per-stream separation comes
    from the ``tweak`` argument of ``tags`` (XORed into the nonce)."""
    key_bytes: bytes
    nonce3: Tuple[int, int, int]

    @property
    def key_words(self):
        return jnp.asarray(C.key_to_words(self.key_bytes[:32]))

    def hash_keys(self, n_words: int):
        return jnp.asarray(_hash_keys_host(self.key_bytes, 2 * n_words))

    def tags(self, ct_words, addrs, wcs, lids=0, tweak=(0, 0, 0)):
        """Tag per trailing-axis message: uhash(ct) ^ pad(addr, wc, lid).
        ``ct_words``: (..., W) u32; addrs/wcs/lids broadcast to (...,)."""
        ct = jnp.asarray(ct_words, jnp.uint32)
        tag = uhash(self.hash_keys(ct.shape[-1]), ct)
        return tag ^ self.pads(addrs, wcs, lids, tweak)

    def pads(self, addrs, wcs, lids=0, tweak=(0, 0, 0)):
        """The Wegman–Carter pads of ``tags``: a stored tag XOR its pad is
        the hash a verifier recomputes over the ciphertext."""
        n3 = tuple(int(a) ^ int(b) for a, b in zip(self.nonce3, tweak))
        return mac_pads(self.key_words, n3, addrs, wcs, lids)


def mac_context(key_bytes: bytes, domain: str) -> MacContext:
    """MAC context with the pad nonce bound to a named domain, disjoint from
    every encryption-nonce domain ("tiles/", "kvcache/", line nonces)."""
    h = hashlib.sha256(b"mac/" + domain.encode()).digest()
    return MacContext(bytes(key_bytes),
                      tuple(int.from_bytes(h[i:i + 4], "little")
                            for i in (20, 24, 28)))


# --------------------------------------------------------------------------
# layout-shaped tag helpers
# --------------------------------------------------------------------------

def tile_tags(ctx: MacContext, ct, row_mask, wc, bk: int, bn: int,
              tweak=(0, 0, 0)):
    """Per-(bk, bn)-tile tags for a tile-sealed weight.

    ct: (..., K, N) u32 ciphertext; row_mask: (..., K) bool SE row flags;
    wc: (...,) write counter per stacked slice. The message is the masked
    ciphertext — SE-plaintext (bypass) rows are zeroed and therefore out of
    MAC scope *by construction*; the pad binds (tile address, wc, tweak).
    Returns (..., K//bk, N//bn) u32. The hash walks one row of tiles at a
    time (and a stack one slice at a time), which bounds its temporaries to
    one (bk, N) row.
    """
    ct = jnp.asarray(ct, jnp.uint32)
    mask = jnp.asarray(row_mask, bool)
    if ct.ndim > 2:
        wcs = jnp.broadcast_to(jnp.asarray(wc, jnp.uint32), ct.shape[:-2])
        return jax.lax.map(lambda a: tile_tags(ctx, *a, bk, bn, tweak),
                           (ct, mask, wcs))
    k, n = ct.shape
    nk, nn = k // bk, n // bn
    keys = ctx.hash_keys(bk * bn)

    def row(args):
        ct_row, mask_row = args                      # (bk, N), (bk,)
        ct_row = jnp.where(mask_row[:, None], ct_row, jnp.uint32(0))
        tiles = jnp.moveaxis(ct_row.reshape(bk, nn, bn), 0, 1)
        return uhash(keys, tiles.reshape(nn, bk * bn))

    tag = jax.lax.map(row, (ct.reshape(nk, bk, n), mask.reshape(nk, bk)))
    addr = jnp.arange(nk * nn, dtype=jnp.uint32).reshape(nk, nn)
    return tag ^ mac_pads(ctx.key_words, tuple(
        int(a) ^ int(b) for a, b in zip(ctx.nonce3, tweak)), addr,
        jnp.asarray(wc, jnp.uint32), 0)


def line_tags(ctx: MacContext, records, tweak=(0, 0, 0)):
    """Per-128B-line tags for the at-rest line layout.

    ``records`` is the FULL stored record per line — data words plus the
    co-located counter/flag word(s) (ColoE's packed 34 words, or the
    counter/direct schemes' 32 data words with the counter word appended) —
    so counter and flag tampering is covered by the hash itself; the pad
    binds the line address and the per-tensor tweak.
    """
    rec = jnp.asarray(records, jnp.uint32)
    addrs = jnp.arange(rec.shape[0], dtype=jnp.uint32)
    return ctx.tags(rec, addrs, 0, 0, tweak)
