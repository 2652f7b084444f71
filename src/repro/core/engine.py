"""Memory-encryption engines — paper §2.3 / §3.2.

Three engines over flat uint32 word buffers (a tensor bitcast to words):

* ``DirectEngine``   — AES-128-ECB on each 16 B block, one global key. The
  paper's low-security baseline (dictionary/retry-attack prone: equal
  plaintext -> equal ciphertext).
* ``CounterEngine``  — counter-mode: OTP = ChaCha20(key, line_addr,
  write_counter); XOR with data. Counters stored in a SEPARATE table
  (extra memory stream -> the paper's +31-35% accesses).
* ``ColoEEngine``    — identical OTP, counters colocated per line in a
  packed 34-word record (single stream; paper's contribution #2).

Security property shared by Counter/ColoE: the (line_addr, write_counter)
pair is never reused for a given key, so OTPs are unique; counters are
stored in plaintext (safe without the key, paper §2.3).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cipher as C
from repro.core import coloe as CL
from repro.core import mac as M


def tensor_to_words(x) -> Tuple[jnp.ndarray, tuple, jnp.dtype]:
    """Bitcast any float/int tensor to a flat u32 word buffer (pads to 4B)."""
    flat = x.reshape(-1)
    dt = flat.dtype
    if dt.itemsize == 4:
        words = jax.lax.bitcast_convert_type(flat, jnp.uint32)
    elif dt.itemsize == 2:
        if flat.shape[0] % 2:
            flat = jnp.concatenate([flat, jnp.zeros((1,), dt)])
        half = jax.lax.bitcast_convert_type(flat, jnp.uint16).reshape(-1, 2)
        words = jax.lax.bitcast_convert_type(half, jnp.uint32).reshape(-1)
    else:
        raise TypeError(f"unsupported dtype {dt}")
    return words.reshape(-1), x.shape, dt


def words_to_tensor(words, shape, dtype):
    dtype = jnp.dtype(dtype)
    n = int(np.prod(shape)) if shape else 1
    if dtype.itemsize == 4:
        flat = jax.lax.bitcast_convert_type(words, dtype)
    elif dtype.itemsize == 2:
        flat = jax.lax.bitcast_convert_type(
            words, jnp.uint16).reshape(-1)
        flat = jax.lax.bitcast_convert_type(flat, dtype)
    else:
        raise TypeError(dtype)
    return flat[:n].reshape(shape)


def _line_otp(key_words, line_addrs, write_counters, nonce2):
    """128 B OTP per line: two ChaCha blocks with
    nonce = (line_addr, nonce2[0], nonce2[1]), counter = wc*2 + subblock."""
    wc = write_counters.astype(jnp.uint32) * jnp.uint32(2)
    nonces = (line_addrs.astype(jnp.uint32), nonce2[0], nonce2[1])
    # word-major halves, one per sub-block, stacked along words: the one
    # transpose at the end folds into the (L, 32) layout, where an (L, 16)
    # intermediate would be materialized lane-padded on a TPU
    halves = [C.chacha20_words(key_words, wc + jnp.uint32(s), nonces)
              for s in range(2)]
    return jnp.concatenate(halves, axis=0).T                 # (L, 32)


@dataclasses.dataclass
class SealedBuffer:
    """Ciphertext + metadata for one tensor (or tensor row-group)."""
    scheme: str                      # direct | counter | coloe
    payload: jnp.ndarray             # direct/counter: (L,32); coloe: (L,34)
    counters: Optional[jnp.ndarray]  # counter scheme: separate (L,) table
    orig_len: int                    # valid words
    shape: tuple
    dtype: object
    nonce2: tuple                    # per-tensor nonce words (static)

    @property
    def n_lines(self) -> int:
        if self.payload is not None:
            return self.payload.shape[0]
        return -(-self.orig_len // CL.WORDS_PER_LINE)

    def data_bytes(self) -> int:
        return self.n_lines * CL.WORDS_PER_LINE * 4

    def stored_bytes(self) -> int:
        if self.scheme == "coloe":
            return self.n_lines * CL.COLOE_LINE_WORDS * 4
        extra = self.n_lines * 8 if self.scheme == "counter" else 0
        return self.data_bytes() + extra

    def extra_streams(self) -> int:
        """Independent memory streams a reader must fetch (1 = colocated)."""
        return 2 if self.scheme == "counter" else 1


class EngineProtocol:
    """What every memory-encryption engine emits.

    * ``encrypt`` / ``decrypt`` — the line-packed at-rest layout (128 B
      lines, counters separate or colocated per scheme).
    * ``encrypt_tiles`` / ``decrypt_tiles`` — the tile-sealed matmul layout
      (counter-mode engines only): a (K, N) weight whose keystream derives
      from the tile address (``kernels.ref.tile_counters``), so any
      (bk, bn) tile decrypts independently inside the fused Pallas kernel.
      ``supports_fused`` gates it — AES-ECB has no counter structure to
      exploit, so Direct stays on the eager line layout.
    * ``seal_cache_blocks`` — the same address-derived-keystream trick
      applied to paged KV-cache blocks (counter-mode engines only): the OTP
      derives from (pool block address, per-block write counter, layer id)
      via ``kernels.ref.cache_block_otp``, so cache blocks written at
      decode time stay ciphertext in HBM and decrypt independently on the
      attention-gather read path. XOR is an involution, so one method both
      seals and unseals.
    * ``line_macs`` / ``verify_lines`` — truncated Carter–Wegman tags over
      the at-rest line records (``core.mac``): the hash covers the FULL
      stored record — data words plus the co-located counter/flag word(s) —
      so bit flips, counter tampering and flag (bypass-bit) flips are all
      caught; the pad binds the line address plus a per-tensor tweak, so
      lines cannot be swapped across addresses or tensors.
    """
    supports_fused = False

    def line_record(self, s: SealedBuffer):
        """The full at-rest record per line — the MAC message. ColoE already
        packs counters+flags in-line; counter/direct append their separate
        counter/flag word so it is covered too."""
        if s.scheme == "coloe":
            return s.payload
        return jnp.concatenate(
            [s.payload, jnp.asarray(s.counters, jnp.uint32)[:, None]], axis=1)

    def line_macs(self, s: SealedBuffer, tweak=(0, 0, 0)):
        return M.line_tags(self.mac_ctx, self.line_record(s), tweak)

    def verify_lines(self, s: SealedBuffer, macs, tweak=(0, 0, 0)):
        """(L,) bool — per-line tag match against the stored MACs."""
        return self.line_macs(s, tweak) == jnp.asarray(macs, jnp.uint32)

    def seal_cache_blocks(self, words, nonce3, block_ids, write_counters,
                          layer_ids):
        raise NotImplementedError(f"{self.name}: no cache-block layout")

    def encrypt_tiles(self, w2d, nonce3, row_mask, write_counter: int,
                      bk: int, bn: int):
        raise NotImplementedError(f"{self.name}: no tile-sealed layout")

    def decrypt_tiles(self, ct2d, nonce3, row_mask, write_counter: int,
                      bk: int, bn: int):
        raise NotImplementedError(f"{self.name}: no tile-sealed layout")


class DirectEngine(EngineProtocol):
    """AES-128-ECB — paper's 'Direct' baseline."""
    name = "direct"

    def __init__(self, key_bytes: bytes):
        self.round_keys = C.aes128_key_schedule(
            np.frombuffer(key_bytes[:16], np.uint8))
        self.mac_ctx = M.mac_context(key_bytes, "weights")

    def encrypt(self, x, nonce2=(0, 0), enc_flags=None) -> SealedBuffer:
        words, shape, dt = tensor_to_words(x)
        lines, orig = CL.pad_to_lines(words)
        by = jax.lax.bitcast_convert_type(lines.reshape(-1), jnp.uint8)
        ct = C.aes128_encrypt_blocks(by.reshape(-1, 16), self.round_keys)
        ctw = jax.lax.bitcast_convert_type(
            ct.reshape(-1, 4), jnp.uint32).reshape(lines.shape)
        if enc_flags is not None:
            enc = (enc_flags & 1).astype(bool)[:, None]
            ctw = jnp.where(enc, ctw, lines)
        flags = (jnp.ones((lines.shape[0],), jnp.uint32) if enc_flags is None
                 else enc_flags.astype(jnp.uint32))
        return SealedBuffer("direct", ctw, flags, orig, shape, dt, (0, 0))

    def decrypt(self, s: SealedBuffer):
        by = jax.lax.bitcast_convert_type(s.payload.reshape(-1), jnp.uint8)
        pt = C.aes128_decrypt_blocks(by.reshape(-1, 16), self.round_keys)
        words = jax.lax.bitcast_convert_type(
            pt.reshape(-1, 4), jnp.uint32).reshape(s.payload.shape)
        if s.counters is not None:     # flags ride in the counters slot
            enc = (s.counters & 1).astype(bool)[:, None]
            words = jnp.where(enc, words, s.payload)
        return words_to_tensor(words.reshape(-1)[:s.orig_len], s.shape, s.dtype)


def _nonce_key(nonce3) -> Tuple[int, ...]:
    return tuple(int(v) for v in np.asarray(nonce3, np.uint32).reshape(-1))


@functools.partial(jax.jit, static_argnames=("nonce3", "bk", "bn",
                                             "decrypt"))
def _tiles_xor(key_words, w, row_mask, write_counter, *, nonce3, bk, bn,
               decrypt):
    """Tile-sealed (un)seal of a (K, N) leaf or a stack of them; the stack
    is walked with ``lax.map`` so only one slice's keystream is live."""
    from repro.kernels import ref as _ref   # oracle owns the derivation
    fn = _ref.unseal_weights_ref if decrypt else _ref.seal_weights_ref
    nonce = jnp.asarray(nonce3, jnp.uint32)

    def one(args):
        w2d, mask, wc = args
        return fn(w2d, key_words, nonce, bk, bn, mask, wc)

    wc = jnp.asarray(write_counter, jnp.uint32)
    if w.ndim == 2:
        return one((w, row_mask, wc))
    wc = jnp.broadcast_to(wc, w.shape[:1])
    return jax.lax.map(one, (w, row_mask, wc))


class _CtrBase(EngineProtocol):
    supports_fused = True

    def __init__(self, key_bytes: bytes):
        self.key_words = jnp.asarray(C.key_to_words(key_bytes[:32]))
        self.mac_ctx = M.mac_context(key_bytes, "weights")

    def _otp(self, n_lines, write_counters, nonce2):
        addrs = jnp.arange(n_lines, dtype=jnp.uint32)
        return _line_otp(self.key_words, addrs, write_counters, nonce2)

    # ---- tile-sealed matmul layout (shared by counter & coloe: the only
    # counter state is the per-tensor write counter, which is colocated by
    # construction — the per-tile counters are implicit in the address) ----

    def encrypt_tiles(self, w, nonce3, row_mask, write_counter, bk: int,
                      bn: int):
        """(..., K, N) float32 -> u32 ciphertext of the same shape; rows
        where ``row_mask`` (..., K) is False stay plaintext (SE bypass,
        paper §3.3). A stacked leaf seals slice by slice under its own
        ``write_counter`` (...,), one slice's keystream at a time."""
        return _tiles_xor(self.key_words, w, row_mask, write_counter,
                          nonce3=_nonce_key(nonce3), bk=bk, bn=bn,
                          decrypt=False)

    def decrypt_tiles(self, ct, nonce3, row_mask, write_counter, bk: int,
                      bn: int):
        return _tiles_xor(self.key_words, ct, row_mask, write_counter,
                          nonce3=_nonce_key(nonce3), bk=bk, bn=bn,
                          decrypt=True)

    # ---- paged KV-cache block layout (cache analogue of the tile scheme:
    # keystream from the block's pool address + write counter + layer id;
    # the serving paths bump a block's counter on every reallocation and on
    # every in-place tail-block rewrite, mirroring ColoE write-backs) ----

    def seal_cache_blocks(self, words, nonce3, block_ids, write_counters,
                          layer_ids):
        """XOR-seal (or unseal) u32 cache-block payloads.

        ``words``: (..., words_per_block) u32; ``block_ids`` /
        ``write_counters`` / ``layer_ids`` broadcast to words.shape[:-1].
        """
        from repro.kernels import ref as _ref
        return jnp.asarray(words, jnp.uint32) ^ _ref.cache_block_otp(
            self.key_words, nonce3, block_ids, write_counters, layer_ids,
            words.shape[-1])

    unseal_cache_blocks = seal_cache_blocks      # XOR involution


class CounterEngine(_CtrBase):
    """Counter-mode with a separate counter table — paper's 'Counter'."""
    name = "counter"

    def encrypt(self, x, nonce2=(1, 2), write_counters=None,
                enc_flags=None) -> SealedBuffer:
        words, shape, dt = tensor_to_words(x)
        lines, orig = CL.pad_to_lines(words)
        L = lines.shape[0]
        wc = (jnp.zeros((L,), jnp.uint32) if write_counters is None
              else write_counters.astype(jnp.uint32))
        if enc_flags is not None:
            # paper §3.3: the spare counter bits carry the emalloc flag; we
            # fold it into bit 31 of the stored counter word.
            wc = wc | ((enc_flags.astype(jnp.uint32) & 1) << 31)
        else:
            wc = wc | jnp.uint32(1 << 31)
        ct_full = lines ^ self._otp(L, wc & jnp.uint32(0x7FFFFFFF), nonce2)
        enc = (wc >> 31).astype(bool)[:, None]
        ct = jnp.where(enc, ct_full, lines)
        return SealedBuffer("counter", ct, wc, orig, shape, dt, tuple(nonce2))

    def decrypt(self, s: SealedBuffer):
        wc = s.counters
        pt_full = s.payload ^ self._otp(
            s.payload.shape[0], wc & jnp.uint32(0x7FFFFFFF), s.nonce2)
        enc = (wc >> 31).astype(bool)[:, None]
        pt = jnp.where(enc, pt_full, s.payload)
        return words_to_tensor(pt.reshape(-1)[:s.orig_len], s.shape, s.dtype)

    def rewrite(self, s: SealedBuffer, x) -> SealedBuffer:
        """Write-back: bump per-line counters so OTPs are never reused."""
        words, shape, dt = tensor_to_words(x)
        lines, orig = CL.pad_to_lines(words)
        flag = s.counters & jnp.uint32(0x80000000)
        wc = ((s.counters & jnp.uint32(0x7FFFFFFF)) + 1) | flag
        ct_full = lines ^ self._otp(lines.shape[0], wc & jnp.uint32(0x7FFFFFFF),
                                    s.nonce2)
        enc = (wc >> 31).astype(bool)[:, None]
        ct = jnp.where(enc, ct_full, lines)
        return SealedBuffer("counter", ct, wc, orig, shape, dt, s.nonce2)


class ColoEEngine(_CtrBase):
    """Colocation-mode — paper's contribution: counters packed in-line."""
    name = "coloe"

    def encrypt(self, x, nonce2=(1, 2), write_counters=None,
                enc_flags=None) -> SealedBuffer:
        words, shape, dt = tensor_to_words(x)
        lines, orig = CL.pad_to_lines(words)
        L = lines.shape[0]
        wc = (jnp.zeros((L,), jnp.uint32) if write_counters is None
              else write_counters.astype(jnp.uint32))
        flags = (jnp.full((L,), CL.FLAG_ENCRYPTED, jnp.uint32)
                 if enc_flags is None else enc_flags.astype(jnp.uint32))
        otp = self._otp(L, wc, nonce2)
        # lines with flag bit 0 cleared (malloc'd, not emalloc'd) bypass the
        # engine — paper §3.3
        enc = (flags & 1).astype(bool)[:, None]
        ct = jnp.where(enc, lines ^ otp, lines)
        packed = CL.coloe_pack(ct, wc, flags)
        return SealedBuffer("coloe", packed, None, orig, shape, dt, tuple(nonce2))

    def decrypt(self, s: SealedBuffer):
        ct, wc, flags = CL.coloe_unpack(s.payload)
        otp = self._otp(ct.shape[0], wc, s.nonce2)
        enc = (flags & 1).astype(bool)[:, None]
        pt = jnp.where(enc, ct ^ otp, ct)
        return words_to_tensor(pt.reshape(-1)[:s.orig_len], s.shape, s.dtype)

    def rewrite(self, s: SealedBuffer, x) -> SealedBuffer:
        _, wc, flags = CL.coloe_unpack(s.payload)
        words, shape, dt = tensor_to_words(x)
        lines, orig = CL.pad_to_lines(words)
        wc = wc + 1
        otp = self._otp(lines.shape[0], wc, s.nonce2)
        enc = (flags & 1).astype(bool)[:, None]
        ct = jnp.where(enc, lines ^ otp, lines)
        return SealedBuffer("coloe", CL.coloe_pack(ct, wc, flags), None,
                            orig, shape, dt, s.nonce2)


def make_engine(mode: str, key_bytes: bytes):
    return {"direct": DirectEngine, "counter": CounterEngine,
            "coloe": ColoEEngine}[mode](key_bytes)
