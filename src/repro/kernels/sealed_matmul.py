"""Pallas TPU kernel: fused decrypt + matmul over sealed (ciphertext) weights.

The paper hides decryption latency inside the memory read (counter-mode OTP
generated in parallel with the DRAM fetch, §2.3). The TPU-native analogue
goes one step further: the ChaCha20 keystream for a weight tile is generated
on the VPU *while that ciphertext tile streams HBM->VMEM for the matmul*,
and the XOR happens in-register immediately before the MXU contraction —

    y[i,j] = sum_k x[i,k] * f32( w_ct[k,j] XOR pad(k,j) )

so sealed weights cost ZERO extra HBM traffic vs. a plain matmul (the
unfused baseline reads ct, writes pt, re-reads pt: 3x weight bytes).

SE integration: ``row_mask[k]`` marks encrypted input rows; plaintext rows
skip the XOR (the paper's emalloc/malloc bypass, §3.3).

Tiling: grid (M/bm, N/bn, K/bk), k-innermost accumulation in the out tile.
BlockSpec tiles live in VMEM; bm/bn/bk default to 128/128/128 (MXU-aligned).
Key, nonce and write counter are scalars in SMEM. Each (bk, bn) tile
consumes bk*bn/16 ChaCha blocks whose counters derive from the tile address
(same derivation as ``ref.tile_counters``), so any tile can be decrypted
independently — this is what makes the layout DMA-friendly and the kernel
grid-parallel. The blocks of a tile are laid out as a (bk/16, bn) plane, so
the 16 ChaCha state words are 16 (bk/16, bn) arrays (one vreg each at
bk = bn = 128) and the pad is their concatenation along rows: word w of
block (s, c) pads element (w * bk/16 + s, c). No relayout is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chacha20 import _chacha_rounds, _CONST


def tile_plaintext(key_ref, nonce_ref, wc, tile_id, w_ref, mask_ref, *, bk,
                   bn, uniq):
    """The plaintext of the (bk, bn) weight tile in ``w_ref`` (u32) under
    write counter ``wc``: its ChaCha pad (``ref.tile_counters``), XORed in
    registers into the rows ``mask_ref`` flags; as f32."""
    rpp = bk // 16                     # rows per keystream-word plane
    nblk = rpp * bn                    # ChaCha blocks per weight tile
    base = wc * jnp.uint32(uniq) + (tile_id * nblk).astype(jnp.uint32)
    ctr = base + (jax.lax.broadcasted_iota(jnp.uint32, (rpp, bn), 0)
                  * jnp.uint32(bn)
                  + jax.lax.broadcasted_iota(jnp.uint32, (rpp, bn), 1))

    init = [jnp.full((rpp, bn), _CONST[i], jnp.uint32) for i in range(4)]
    init += [jnp.full((rpp, bn), key_ref[i], jnp.uint32) for i in range(8)]
    init.append(ctr)
    init += [jnp.full((rpp, bn), nonce_ref[i], jnp.uint32) for i in range(3)]
    x16 = _chacha_rounds(list(init))
    pad = jnp.concatenate([x16[i] + init[i] for i in range(16)], axis=0)
    wu = w_ref[...]
    wpt = jnp.where(mask_ref[...] != 0, wu ^ pad, wu)
    return jax.lax.bitcast_convert_type(wpt, jnp.float32)


def _make_kernel(bk, bn, nn_tiles, uniq, compute_dtype):
    cdt = jnp.dtype(compute_dtype)

    def kernel(key_ref, nonce_ref, wc_ref, x_ref, w_ref, mask_ref, out_ref):
        j_idx = pl.program_id(1)
        k_idx = pl.program_id(2)
        tile_id = k_idx * nn_tiles + j_idx
        # match the unfused model path's precision: weights/activations are
        # rounded to the model compute dtype before the MXU contraction,
        # which always accumulates in f32
        wf = tile_plaintext(key_ref, nonce_ref, wc_ref[0], tile_id, w_ref,
                            mask_ref, bk=bk, bn=bn, uniq=uniq).astype(cdt)
        acc = jnp.dot(x_ref[...].astype(cdt), wf,
                      preferred_element_type=jnp.float32)

        @pl.when(k_idx == 0)
        def _init():
            out_ref[...] = acc

        @pl.when(k_idx != 0)
        def _acc():
            out_ref[...] += acc

    return kernel


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret",
                                             "compute_dtype"))
def sealed_matmul(x, w_ct, row_mask, key_words, nonce_words, write_counter,
                  *, bm: int, bk: int, bn: int, interpret: bool,
                  compute_dtype: str = "float32"):
    """x: (M, K) f32; w_ct: (K, N) u32 (tile-sealed, see kernels.ref);
    row_mask: (K,) bool (True = row is ciphertext);
    write_counter: (1,) u32. Returns (M, N) f32, accumulated in f32 with
    operands rounded to ``compute_dtype`` (the model compute precision)."""
    m, k = x.shape
    k2, n = w_ct.shape
    assert k == k2 and m % bm == 0 and k % bk == 0 and n % bn == 0, \
        (x.shape, w_ct.shape, bm, bk, bn)
    assert bk % 16 == 0, bk
    nn_tiles = n // bn
    uniq = (k * n) // 16
    kernel = _make_kernel(bk, bn, nn_tiles, uniq, compute_dtype)
    grid = (m // bm, n // bn, k // bk)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            smem, smem, smem,
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            # the SE row mask as an i32 column: one flag per weight row,
            # already on the sublane axis the XOR select broadcasts along
            pl.BlockSpec((bk, 1), lambda i, j, kk: (kk, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="sealed_matmul",
    )(jnp.asarray(key_words, jnp.uint32), jnp.asarray(nonce_words, jnp.uint32),
      jnp.asarray(write_counter, jnp.uint32).reshape(1),
      x.astype(jnp.float32), w_ct.astype(jnp.uint32),
      jnp.asarray(row_mask).astype(jnp.int32).reshape(k, 1))
