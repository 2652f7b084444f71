"""``SealedTensor`` — a first-class, jit-traversable ciphertext tensor.

This is the pytree node that lets sealed weights flow through ``jax.jit``,
``jax.lax.scan`` and the model code *without being decrypted first*. It
replaces the old ``buffers``-dict + ``metas``-with-``payload=None`` split in
``sealed_store``: the traced children (ciphertext payload, counter table, SE
row mask, key words, write counter) and the static layout metadata travel
together as one object.

Two layouts:

* ``"lines"`` — the at-rest HBM image (paper §2.3/§3.2): payload is
  ``(L, 32)`` u32 data lines (direct/counter schemes) or ``(L, 34)`` ColoE
  records with the counter+flag words packed in-line. Decrypted eagerly
  (``sealed_store.unseal_params``) before use.

* ``"tiles"`` — the matmul operand layout: payload is the logical weight
  bitcast to u32 *in its original shape*, encrypted so that every
  ``(bk, bn)`` tile's keystream derives purely from the tile address
  (``kernels.ref.tile_counters``). Any tile decrypts independently, which is
  what lets ``kernels.sealed_matmul`` XOR the pad in-register while the
  ciphertext tile streams toward the MXU — zero extra HBM traffic, and the
  plaintext weight never materializes in memory.

Scan compatibility: for layer-stacked leaves every child carries the stack
axis in front (payload ``(n, ...)``, row_mask ``(n, K)``, key ``(n, 8)``,
wc ``(n,)``), so ``lax.scan`` slices a per-layer ``SealedTensor`` out of the
stacked one with the SAME static metadata. ``matmul`` detects the sliced
form by rank. Each stack slice is sealed under its own write-counter so the
(key, nonce, counter) triple — and hence the OTP — is never reused across
layers.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SealMeta:
    """Static (hashable) layout metadata carried as pytree aux_data."""
    scheme: str                    # direct | counter | coloe
    layout: str                    # lines | tiles
    dtype: str                     # original leaf dtype string
    nonce: Tuple[int, ...]         # 2 words (lines) / 3 words (tiles)
    shape: Tuple[int, ...]         # logical (stacked) leaf shape
    orig_len: int = 0              # valid words (lines layout)
    n_batch: int = 0               # tiles: leading stack axes at seal time
    k_ndim: int = 1                # tiles: contraction (row) axes
    n_out: int = 1                 # tiles: trailing output axes
    bk: int = 128                  # tiles: contraction tile
    bn: int = 128                  # tiles: output tile
    fused: bool = False            # tiles: reaches its matmul still sealed


class SealedTensor:
    """Ciphertext leaf. Children are traced; ``meta`` is static.

    payload:   u32 ciphertext (layout-dependent shape, see module doc)
    counters:  separate (L,) table — counter scheme's "lines" layout only
    row_mask:  (batch..., K) bool — SE row flags, "tiles" layout only
    key_words: (batch..., 8) u32 — cipher key, "tiles" layout only
    wc:        (batch...,) u32 — per-slice write counter, "tiles" only
    macs:      u32 Carter–Wegman tags co-located with the counter metadata
               (lines: (L,) per 128 B line; tiles: (batch..., K//bk, N//bn)
               per tile). None when the store was sealed without integrity.
    """

    __slots__ = ("payload", "counters", "row_mask", "key_words", "wc", "meta",
                 "macs")

    def __init__(self, payload, counters, row_mask, key_words, wc,
                 meta: SealMeta, macs=None):
        self.payload = payload
        self.counters = counters
        self.row_mask = row_mask
        self.key_words = key_words
        self.wc = wc
        self.meta = meta
        self.macs = macs

    # ---- structure ----

    def tree_flatten(self):
        return ((self.payload, self.counters, self.row_mask, self.key_words,
                 self.wc, self.macs), self.meta)

    @classmethod
    def tree_unflatten(cls, meta, children):
        return cls(*children[:5], meta=meta, macs=children[5])

    def __repr__(self):
        p = getattr(self.payload, "shape", None)
        return (f"SealedTensor({self.meta.scheme}/{self.meta.layout}, "
                f"payload={p}, shape={self.meta.shape})")

    # ---- tiles-layout geometry ----

    @property
    def sliced(self) -> bool:
        """True once the stack axes were consumed (inside a layer scan)."""
        m = self.meta
        return self.payload.ndim == m.k_ndim + m.n_out

    @property
    def out_shape(self) -> Tuple[int, ...]:
        return tuple(self.payload.shape[-self.meta.n_out:])

    @property
    def k_size(self) -> int:
        m = self.meta
        return int(np.prod(self.payload.shape[-(m.k_ndim + m.n_out):
                                              -m.n_out]))

    @property
    def n_size(self) -> int:
        return int(np.prod(self.out_shape))

    def logical_bytes(self) -> int:
        return int(np.prod(self.meta.shape)) * jnp.dtype(self.meta.dtype).itemsize

    def stored_bytes(self) -> int:
        """Bytes of the at-rest image (counters/flags/MACs included)."""
        mac_b = self.macs.size * 4 if self.macs is not None else 0
        if self.meta.layout == "tiles":
            b = self.payload.size * 4
            if self.row_mask is not None:
                b += self.row_mask.size          # 1 B/row SE flag
            if self.wc is not None:
                b += max(self.wc.size, 1) * 4    # write counters
            return b + mac_b
        n_lines = self.payload.shape[0]
        if self.meta.scheme == "coloe":
            return n_lines * self.payload.shape[1] * 4 + mac_b
        extra = n_lines * 8 if self.meta.scheme == "counter" else 0
        return n_lines * 32 * 4 + extra + mac_b

    def extra_streams(self) -> int:
        """Independent HBM streams a reader must fetch (1 = colocated).

        The tile layout is inherently colocated: the only counter state is
        the per-slice write counter word; line counters are implicit in the
        tile address."""
        return 2 if (self.meta.layout == "lines"
                     and self.meta.scheme == "counter") else 1

    # ---- consumption ----

    def matmul(self, x2d, *, compute_dtype: str = "float32",
               interpret=None):
        """Fused decrypt-in-matmul: ``x2d @ decrypt(payload)`` without ever
        materializing the plaintext weight in HBM.

        x2d: (M, K) activations; returns (M, N) f32. Tiles layout only, and
        only once the stack axes have been sliced away (inside the layer
        scan) or for unstacked leaves.
        """
        m = self.meta
        if m.layout != "tiles":
            raise ValueError("matmul needs the tile-sealed layout")
        if not self.sliced:
            raise ValueError(
                f"stacked SealedTensor {self.payload.shape}: slice the "
                f"{m.n_batch} stack axis/axes (lax.scan) before matmul")
        from repro.kernels import ops   # deferred: core must import cheaply
        wct = self.payload.reshape(self.k_size, self.n_size)
        mask = self.row_mask.reshape(self.k_size)
        return ops.sealed_matmul(
            x2d, wct, mask,
            self.key_words.reshape(8),
            jnp.asarray(m.nonce, jnp.uint32),
            write_counter=jnp.reshape(self.wc, ()),
            bk=m.bk, bn=m.bn, compute_dtype=compute_dtype,
            interpret=interpret)


    def gmm(self, xs, *, compute_dtype: str = "float32", interpret=None):
        """Fused decrypt-in-grouped-matmul over one layer's expert stack:
        ``xs`` (E, T, K), expert e's slab, against expert e's (K, N)
        weight, each decrypted in-register by ``kernels.sealed_gmm`` under
        its own write counter. Tiles layout with an expert axis under the
        layer axis, once the layer axis was sliced away."""
        m = self.meta
        if m.layout != "tiles" or m.n_batch != 2 or \
                self.payload.ndim != m.k_ndim + m.n_out + 1:
            raise ValueError(f"gmm needs one layer of a tile-sealed expert "
                             f"stack, got {self!r}")
        from repro.kernels import ops   # deferred: core must import cheaply
        e = self.payload.shape[0]
        return ops.sealed_gmm(
            xs, self.payload.reshape(e, self.k_size, self.n_size),
            self.row_mask.reshape(e, self.k_size),
            self.key_words.reshape(e, 8)[0],
            jnp.asarray(m.nonce, jnp.uint32),
            write_counters=self.wc.reshape(e), bk=m.bk, bn=m.bn,
            compute_dtype=compute_dtype, interpret=interpret)


jax.tree_util.register_pytree_node(
    SealedTensor,
    lambda st: st.tree_flatten(),
    SealedTensor.tree_unflatten)
