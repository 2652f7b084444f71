"""Pallas TPU kernel: grouped matmul over a stack of sealed expert weights.

The expert layer's counterpart of ``sealed_matmul``: the tokens routed to
each held expert arrive grouped, one slab of rows per expert, and expert e's
slab is multiplied by expert e's weight, whose tiles stream HBM->VMEM still
sealed and are decrypted in registers right before the MXU:

    y[e, t, j] = sum_k x[e, t, k] * f32( w_ct[e, k, j] XOR pad_e(k, j) )

Each expert slice is tile-sealed under its own write counter ``wc[e]``
(the stack slice index: layer and expert), so no two experts share a pad;
the pad of a tile is ``sealed_matmul.tile_plaintext``'s, derived from the
tile address as in ``ref.tile_counters``.

Grid (E, N/bn, K/bk), k innermost: each weight tile is read once, and the
(T, bn) output tile of an expert stays in VMEM across the contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sealed_matmul import tile_plaintext


def _make_kernel(bk, bn, nn_tiles, uniq, compute_dtype):
    cdt = jnp.dtype(compute_dtype)

    def kernel(key_ref, nonce_ref, wc_ref, x_ref, w_ref, mask_ref, out_ref):
        e_idx = pl.program_id(0)
        j_idx = pl.program_id(1)
        k_idx = pl.program_id(2)
        wf = tile_plaintext(key_ref, nonce_ref, wc_ref[e_idx],
                            k_idx * nn_tiles + j_idx, w_ref, mask_ref,
                            bk=bk, bn=bn, uniq=uniq).astype(cdt)
        acc = jnp.dot(x_ref[...].astype(cdt), wf,
                      preferred_element_type=jnp.float32)

        @pl.when(k_idx == 0)
        def _init():
            out_ref[...] = acc

        @pl.when(k_idx != 0)
        def _acc():
            out_ref[...] += acc

    return kernel


@functools.partial(jax.jit, static_argnames=("bk", "bn", "interpret",
                                             "compute_dtype"))
def sealed_gmm(x, w_ct, row_mask, key_words, nonce_words, write_counters, *,
               bk: int, bn: int, interpret: bool,
               compute_dtype: str = "float32"):
    """x: (E, T, K) activations, expert e's slab of T rows; w_ct: (E, K, N)
    u32, each slice tile-sealed under ``write_counters[e]``; row_mask:
    (E, K) bool (True = row is ciphertext). Returns (E, T, N) f32,
    accumulated in f32 with operands rounded to ``compute_dtype``."""
    e, t, k = x.shape
    e2, k2, n = w_ct.shape
    assert e == e2 and k == k2 and t % 8 == 0 and k % bk == 0 \
        and n % bn == 0 and bk % 16 == 0, (x.shape, w_ct.shape, bk, bn)
    nn_tiles = n // bn
    kernel = _make_kernel(bk, bn, nn_tiles, (k * n) // 16, compute_dtype)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(e, nn_tiles, k // bk),
        in_specs=[
            smem, smem, smem,
            pl.BlockSpec((None, t, bk), lambda g, j, kk: (g, 0, kk)),
            pl.BlockSpec((None, bk, bn), lambda g, j, kk: (g, kk, j)),
            pl.BlockSpec((None, bk, 1), lambda g, j, kk: (g, kk, 0)),
        ],
        out_specs=pl.BlockSpec((None, t, bn), lambda g, j, kk: (g, 0, j)),
        out_shape=jax.ShapeDtypeStruct((e, t, n), jnp.float32),
        interpret=interpret,
        name="sealed_gmm",
    )(jnp.asarray(key_words, jnp.uint32), jnp.asarray(nonce_words, jnp.uint32),
      jnp.asarray(write_counters, jnp.uint32).reshape(e),
      x, w_ct.astype(jnp.uint32),
      jnp.asarray(row_mask).astype(jnp.int32).reshape(e, k, 1))
