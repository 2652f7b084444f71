"""Block/stream ciphers used by the SEAL engines.

* AES-128 (CTR): the paper's cipher. Pure-jnp T-free implementation (S-box
  via gather) — this is the *reference oracle*; its byte-wise S-box does not
  map onto the TPU VPU (no efficient byte gather), which is exactly why the
  production engine uses ChaCha20 (DESIGN.md §2).
* ChaCha20: 32-bit add-rotate-xor — VPU-native. jnp version here is the
  oracle for the Pallas kernel in ``repro.kernels.chacha20``.

Both validated against published test vectors (FIPS-197 / RFC 7539) in
``tests/test_cipher.py``.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

# ==========================================================================
# AES-128
# ==========================================================================

def _gf_mul(a: int, b: int) -> int:
    r = 0
    for _ in range(8):
        if b & 1:
            r ^= a
        hi = a & 0x80
        a = (a << 1) & 0xFF
        if hi:
            a ^= 0x1B
        b >>= 1
    return r


def _build_sbox() -> np.ndarray:
    # multiplicative inverse in GF(2^8) + affine transform (FIPS-197 §5.1.1)
    inv = np.zeros(256, np.uint8)
    for x in range(1, 256):
        for y in range(1, 256):
            if _gf_mul(x, y) == 1:
                inv[x] = y
                break
    sbox = np.zeros(256, np.uint8)
    for x in range(256):
        b = int(inv[x])
        s = 0
        for i in range(8):
            bit = ((b >> i) ^ (b >> ((i + 4) % 8)) ^ (b >> ((i + 5) % 8)) ^
                   (b >> ((i + 6) % 8)) ^ (b >> ((i + 7) % 8)) ^ (0x63 >> i)) & 1
            s |= bit << i
        sbox[x] = s
    return sbox


SBOX = _build_sbox()
_SBOX_J = jnp.asarray(SBOX)

# xtime (multiply by 2 in GF(2^8)) lookup
_XT = np.array([((x << 1) ^ (0x1B if x & 0x80 else 0)) & 0xFF for x in range(256)],
               np.uint8)
_XT_J = jnp.asarray(_XT)

# ShiftRows permutation on flat column-major state: out[r+4c] = in[r+4((c+r)%4)]
_SHIFT = np.array([(r + 4 * ((c + r) % 4)) % 16 for c in range(4) for r in range(4)],
                  np.int32)
_SHIFT_J = jnp.asarray(_SHIFT)

_RCON = np.array([0x01, 0x02, 0x04, 0x08, 0x10, 0x20, 0x40, 0x80, 0x1B, 0x36],
                 np.uint8)


def aes128_key_schedule(key: np.ndarray) -> np.ndarray:
    """key: (16,) uint8 -> round keys (11, 16) uint8. Host-side (numpy)."""
    key = np.asarray(key, np.uint8).reshape(16)
    w = [key[4 * i:4 * i + 4].copy() for i in range(4)]
    for i in range(4, 44):
        t = w[i - 1].copy()
        if i % 4 == 0:
            t = np.roll(t, -1)
            t = SBOX[t]
            t[0] ^= _RCON[i // 4 - 1]
        w.append(w[i - 4] ^ t)
    return np.stack([np.concatenate(w[4 * r:4 * r + 4]) for r in range(11)])


def _sub_bytes(s):
    return _SBOX_J[s]


def _shift_rows(s):
    return s[..., _SHIFT_J]


def _mix_columns(s):
    # s: (..., 16) uint8, column-major
    v = s.reshape(s.shape[:-1] + (4, 4))            # (..., col, row)
    a0, a1, a2, a3 = v[..., 0], v[..., 1], v[..., 2], v[..., 3]
    x0, x1, x2, x3 = _XT_J[a0], _XT_J[a1], _XT_J[a2], _XT_J[a3]
    r0 = x0 ^ (x1 ^ a1) ^ a2 ^ a3
    r1 = a0 ^ x1 ^ (x2 ^ a2) ^ a3
    r2 = a0 ^ a1 ^ x2 ^ (x3 ^ a3)
    r3 = (x0 ^ a0) ^ a1 ^ a2 ^ x3
    return jnp.stack([r0, r1, r2, r3], axis=-1).reshape(s.shape)


def aes128_encrypt_blocks(blocks, round_keys):
    """blocks: (..., 16) uint8; round_keys: (11, 16) uint8 -> (..., 16)."""
    rk = jnp.asarray(round_keys, jnp.uint8)
    s = blocks ^ rk[0]
    for r in range(1, 10):
        s = _mix_columns(_shift_rows(_sub_bytes(s))) ^ rk[r]
    s = _shift_rows(_sub_bytes(s)) ^ rk[10]
    return s


def aes128_ctr_keystream(round_keys, block_ids, tweak: int = 0):
    """CTR keystream: block i pad = AES(tweak_hi64 || ctr_lo64(block_ids)).

    block_ids: (n,) uint32 -> (n, 16) uint8 keystream. ``tweak`` carries the
    memory-line address so identical counters at different addresses produce
    different OTPs (paper §2.3).
    """
    n = block_ids.shape[0]
    ctr = jnp.zeros((n, 16), jnp.uint8)
    bid = block_ids.astype(jnp.uint32)
    for b in range(4):
        ctr = ctr.at[:, b].set(((bid >> (8 * b)) & 0xFF).astype(jnp.uint8))
    tw = np.frombuffer(np.uint64(tweak).tobytes(), np.uint8)
    ctr = ctr.at[:, 8:16].set(jnp.asarray(tw))
    return aes128_encrypt_blocks(ctr, round_keys)


# ---- AES-128 decryption (needed only by the Direct/ECB engine) ----------

_INV_SBOX = np.zeros(256, np.uint8)
_INV_SBOX[SBOX] = np.arange(256, dtype=np.uint8)
_INV_SBOX_J = jnp.asarray(_INV_SBOX)

_INV_SHIFT = np.zeros(16, np.int32)
_INV_SHIFT[_SHIFT] = np.arange(16)
_INV_SHIFT_J = jnp.asarray(_INV_SHIFT)

_MUL = {m: jnp.asarray(np.array([_gf_mul(x, m) for x in range(256)], np.uint8))
        for m in (9, 11, 13, 14)}


def _inv_mix_columns(s):
    v = s.reshape(s.shape[:-1] + (4, 4))
    a0, a1, a2, a3 = v[..., 0], v[..., 1], v[..., 2], v[..., 3]
    r0 = _MUL[14][a0] ^ _MUL[11][a1] ^ _MUL[13][a2] ^ _MUL[9][a3]
    r1 = _MUL[9][a0] ^ _MUL[14][a1] ^ _MUL[11][a2] ^ _MUL[13][a3]
    r2 = _MUL[13][a0] ^ _MUL[9][a1] ^ _MUL[14][a2] ^ _MUL[11][a3]
    r3 = _MUL[11][a0] ^ _MUL[13][a1] ^ _MUL[9][a2] ^ _MUL[14][a3]
    return jnp.stack([r0, r1, r2, r3], axis=-1).reshape(s.shape)


def aes128_decrypt_blocks(blocks, round_keys):
    rk = jnp.asarray(round_keys, jnp.uint8)
    s = blocks ^ rk[10]
    for r in range(9, 0, -1):
        s = _INV_SBOX_J[s[..., _INV_SHIFT_J]]
        s = _inv_mix_columns(s ^ rk[r])
    s = _INV_SBOX_J[s[..., _INV_SHIFT_J]] ^ rk[0]
    return s


# ==========================================================================
# ChaCha20 (RFC 7539)
# ==========================================================================

_CHACHA_CONST = np.frombuffer(b"expa" + b"nd 3" + b"2-by" + b"te k",
                              np.uint32).copy()


def _rotl32(x, n):
    return (x << n) | (x >> (32 - n))


def _quarter(a, b, c, d):
    a = a + b; d = _rotl32(d ^ a, 16)
    c = c + d; b = _rotl32(b ^ c, 12)
    a = a + b; d = _rotl32(d ^ a, 8)
    c = c + d; b = _rotl32(b ^ c, 7)
    return a, b, c, d


def chacha20_block(key_words, counters, nonce_words):
    """ChaCha20 keystream blocks: (n, 16) uint32 (= n x 64B keystream).
    Arguments as for ``chacha20_words``."""
    return chacha20_words(key_words, counters, nonce_words).T


def chacha20_words(key_words, counters, nonce_words):
    """ChaCha20 keystream blocks, word-major: (16, n) uint32, row w holding
    word w of every block. A TPU lays an (n, 16) array out padded to 128
    lanes, 8x its size; consumers that can take the words as rows avoid it.

    key_words: (8,) uint32; counters: (n,) uint32;
    nonce_words: (3,) uint32 (shared), (n, 3) uint32 (per block), or a
    tuple of three words, each a scalar or an (n,) vector (used by the
    engines to fold the line address + write-counter into the OTP; separate
    vectors avoid the (n, 3) array, which a TPU pads to 128 lanes).
    """
    n = counters.shape[0]
    key_words = jnp.asarray(key_words, jnp.uint32)
    if isinstance(nonce_words, (tuple, list)):
        nonce = [jnp.asarray(v, jnp.uint32) for v in nonce_words]
    else:                                   # (3,) shared or (n, 3) per block
        nonce_words = jnp.asarray(nonce_words, jnp.uint32)
        nonce = [nonce_words[..., i] for i in range(3)]
    state = [jnp.broadcast_to(jnp.uint32(_CHACHA_CONST[i]), (n,)) for i in range(4)]
    state += [jnp.broadcast_to(key_words[i], (n,)) for i in range(8)]
    state += [counters.astype(jnp.uint32)]
    state += [jnp.broadcast_to(v, (n,)) for v in nonce]

    col = ((0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10, 14), (3, 7, 11, 15))
    diag = ((0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13), (3, 4, 9, 14))

    def dround(_, x):
        # rolled into a fori_loop: keeps the HLO ~10x smaller, which is what
        # makes per-step in-graph decryption of a whole model compilable.
        # The 16 state words are carried as separate (n,) vectors, so a
        # round is elementwise work only (no gathers or scatters).
        x = list(x)
        for a, b, c, d in col + diag:
            x[a], x[b], x[c], x[d] = _quarter(x[a], x[b], x[c], x[d])
        return tuple(x)

    x = jax.lax.fori_loop(0, 10, dround, tuple(state))
    return jnp.stack([xi + si for xi, si in zip(x, state)], axis=0)


def chacha20_keystream_u32(key_words, n_words: int, nonce_words, counter0: int = 0):
    """Convenience: n_words uint32 of keystream (padded up to 16-word blocks)."""
    nblk = -(-n_words // 16)
    ctr = jnp.arange(counter0, counter0 + nblk, dtype=jnp.uint32)
    ks = chacha20_block(key_words, ctr, nonce_words)
    return ks.reshape(-1)[:n_words]


def key_to_words(key_bytes: bytes) -> np.ndarray:
    assert len(key_bytes) == 32
    return np.frombuffer(key_bytes, np.uint32).copy()


def derive_nonce(tensor_id: int) -> np.ndarray:
    """Per-tensor nonce from a stable tensor id (path hash)."""
    rng = np.random.RandomState(tensor_id & 0x7FFFFFFF)
    return rng.randint(0, 2**31, size=3).astype(np.uint32)
