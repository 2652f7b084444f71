"""Pallas TPU kernel: ChaCha20-CTR keystream generation.

This is the accelerator-side "encryption engine" of the paper, re-designed
for the TPU VPU (DESIGN.md §2): AES's byte-wise S-box needs hardware byte
gathers the VPU lacks; ChaCha20 is pure 32-bit add/rotate/xor — exactly one
VPU op per primitive. The kernel materializes the 16-word cipher state as
16 (R, 128) planes (sublane x lane), so every quarter-round is a dense
full-vreg VPU op and blocks stream at register bandwidth.

Layout: out[word, block] (16, N) uint32 — word-major so the XOR consumer
can bitcast columns back to 64-byte blocks without a transpose inside VMEM.

Validated against the pure-jnp RFC-7539 oracle (``repro.kernels.ref``) in
interpret mode; tests sweep block counts and tile sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

_CONST = np.frombuffer(b"expand 32-byte k", np.uint32).copy()


def _rotl(x, n):
    return (x << jnp.uint32(n)) | (x >> jnp.uint32(32 - n))


def _qr(a, b, c, d):
    a = a + b
    d = _rotl(d ^ a, 16)
    c = c + d
    b = _rotl(b ^ c, 12)
    a = a + b
    d = _rotl(d ^ a, 8)
    c = c + d
    b = _rotl(b ^ c, 7)
    return a, b, c, d


def _double_round(x):
    """x: list of 16 (T,) vectors -> after one column and one diagonal
    round."""
    x[0], x[4], x[8], x[12] = _qr(x[0], x[4], x[8], x[12])
    x[1], x[5], x[9], x[13] = _qr(x[1], x[5], x[9], x[13])
    x[2], x[6], x[10], x[14] = _qr(x[2], x[6], x[10], x[14])
    x[3], x[7], x[11], x[15] = _qr(x[3], x[7], x[11], x[15])
    x[0], x[5], x[10], x[15] = _qr(x[0], x[5], x[10], x[15])
    x[1], x[6], x[11], x[12] = _qr(x[1], x[6], x[11], x[12])
    x[2], x[7], x[8], x[13] = _qr(x[2], x[7], x[8], x[13])
    x[3], x[4], x[9], x[14] = _qr(x[3], x[4], x[9], x[14])
    return x


def _chacha_rounds(x):
    """x: list of 16 (T,) vectors -> after 20 rounds (pre-add)."""
    for _ in range(10):
        x = _double_round(x)
    return x


def _keystream_kernel(key_ref, nonce_ref, ctr_ref, out_ref):
    """One grid step: a (R, L) plane of keystream blocks.

    key_ref: (8,) u32 and nonce_ref: (3,) u32, both in SMEM; ctr_ref:
    (R, L) u32 counters; out_ref: (16, R, L) u32, one plane per state word.
    """
    shape = ctr_ref.shape
    init = [jnp.full(shape, _CONST[i], jnp.uint32) for i in range(4)]
    init += [jnp.full(shape, key_ref[i], jnp.uint32) for i in range(8)]
    init.append(ctr_ref[...])
    init += [jnp.full(shape, nonce_ref[i], jnp.uint32) for i in range(3)]
    x = _chacha_rounds(list(init))
    for i in range(16):
        out_ref[i] = x[i] + init[i]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def chacha20_keystream(key_words, nonce_words, counters, *, tile: int,
                       interpret: bool):
    """Keystream blocks for the given counters.

    key_words: (8,) u32; nonce_words: (3,) u32; counters: (N,) u32 with
    N % tile == 0. Returns (16, N) u32 — 64 bytes per column.

    The counters are laid out 2-D, (N/L, L) with L = min(tile, 128) lanes,
    and a grid step takes tile/L rows of them. On the TPU a tile is a
    multiple of 1024, so each step covers whole (8, 128) u32 vregs.
    """
    n = counters.shape[0]
    assert n % tile == 0, (n, tile)
    lanes = min(tile, 128)
    assert tile % lanes == 0, (tile, lanes)
    rows = tile // lanes
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _keystream_kernel,
        grid=(n // tile,),
        in_specs=[smem, smem, pl.BlockSpec((rows, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((16, rows, lanes), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((16, n // lanes, lanes), jnp.uint32),
        interpret=interpret,
        name="chacha20_keystream",
    )(key_words.astype(jnp.uint32), nonce_words.astype(jnp.uint32),
      counters.astype(jnp.uint32).reshape(n // lanes, lanes))
    return out.reshape(16, n)
