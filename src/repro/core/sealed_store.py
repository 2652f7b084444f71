"""Sealed parameter store: keep model weights as ciphertext (the HBM/at-rest
image an adversary could probe — DESIGN.md §2) and decrypt on use.

``seal_params`` applies the SE plan (which rows are ciphertext) + the chosen
engine (direct / counter / coloe) per leaf, producing one ``SealedTensor``
per leaf:

* matmul-shaped leaves (attention wq/wk/wv/wo, MLA's wkv_a/wk_rope/wk_b/wv_b,
  dense- and shared-MLP wi/wg/wo, the LM head) get the **tile-sealed
  layout** when ``seal.fuse_decrypt`` is on and the engine is counter-mode:
  they flow *still sealed* through the jitted serving graph into
  ``kernels.sealed_matmul`` and are decrypted in-register under their SE
  row masks — the plaintext weight never exists in HBM;
* the held experts of a dropless MoE layer (``experts/{wi,wg,wo}``, a
  layer x expert stack) are tile-sealed per (layer, expert) and flow still
  sealed into ``kernels.sealed_gmm`` the same way;
* the token embedding is tile-sealed too (an unpadded layout on a TPU)
  but decrypted eagerly in-graph, since its consumer is a gather;
* everything else (norms, routers, capacity-routed MoE experts,
  recurrent/SSM weights) gets the **line-packed at-rest layout** and is
  decrypted eagerly in-graph.

``unseal_params`` decrypts every leaf (both layouts, jittable);
``fused_params`` passes the matmul leaves through as ``SealedTensor`` and
decrypts the rest — that is the serving hot path, and
``plaintext_bytes_materialized`` is exactly the per-step metric it buys.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import SealConfig
from repro.core import coloe as CL
from repro.core import engine as E
from repro.core import mac as M
from repro.core import plan as P
from repro.core.sealed_tensor import SealedTensor, SealMeta


@dataclasses.dataclass
class SealedParams:
    """tensors: path -> SealedTensor (jit-traversable pytree); plans and
    treedef are static host metadata."""
    tensors: Dict[str, SealedTensor]
    plans: Dict[str, P.LeafPlan]
    treedef: object
    seal: SealConfig

    def stored_bytes(self) -> int:
        return sum(t.stored_bytes() for t in self.tensors.values())

    def enc_fraction(self) -> float:
        return P.plan_totals(self.plans)["enc_fraction"]

    def fused_paths(self):
        return [p for p, t in self.tensors.items() if t.meta.fused]

    def plaintext_bytes_materialized(self) -> int:
        """Plaintext bytes the decrypt-on-use graph materializes per step:
        only the eagerly-decrypted leaf fraction; fused leaves are decrypted
        in-register inside the matmul."""
        fused = set(self.fused_paths())
        return sum(t.logical_bytes() for p, t in self.tensors.items()
                   if p not in fused)


def _nonce2(path: str) -> Tuple[int, int]:
    h = hashlib.sha256(path.encode()).digest()
    return (int.from_bytes(h[:4], "little"), int.from_bytes(h[4:8], "little"))


def _nonce3(path: str) -> Tuple[int, int, int]:
    """3-word per-tensor nonce for the tile layout (distinct domain from the
    line layout, whose nonce word 0 is the small line address)."""
    h = hashlib.sha256(b"tiles/" + path.encode()).digest()
    return tuple(int.from_bytes(h[i:i + 4], "little") | 1
                 for i in (8, 12, 16))


def _line_tweak(path: str) -> Tuple[int, int, int]:
    """Per-tensor MAC-pad tweak for line-layout leaves. Word 2 stays 0 while
    every tile nonce word is forced odd, so line and tile tag domains can
    never collide even across tensors."""
    return _nonce2(path) + (0,)


@dataclasses.dataclass(frozen=True)
class CacheSeal:
    """Static sealing context for the paged KV cache: key words plus one
    3-word nonce per stream (k / v). Layer identity and write counters are
    folded in per block by ``kernels.ref.cache_block_otp``; the k/v nonces
    keep the two streams in disjoint keystream domains even at the same
    (block, layer, counter) address."""
    key_words: object                 # (8,) u32
    nonce_k: Tuple[int, int, int]
    nonce_v: Tuple[int, int, int]
    # integrity: when set, every pool block carries a co-located MAC word
    # per stream (``mac_k``/``mac_v``), written on every sealed write and
    # checked on every gather/read (``models/paged.py``)
    mac: Optional[M.MacContext] = None
    # the nonce of an MLA pool's one latent stream
    nonce_c: Tuple[int, int, int] = (0, 0, 0)

    def nonce(self, stream: str) -> Tuple[int, int, int]:
        """The nonce of pool stream ``stream`` ("k", "v" or "c")."""
        return {"k": self.nonce_k, "v": self.nonce_v,
                "c": self.nonce_c}[stream]


def cache_seal_config(key_bytes: bytes, verify: bool = False) -> CacheSeal:
    """Build the cache-block sealing context (same key as the weight store,
    distinct nonce domain — "kvcache/" vs "tiles/"). ``verify`` arms the
    per-block Carter–Wegman MACs."""
    from repro.core import cipher as C
    return CacheSeal(jnp.asarray(C.key_to_words(key_bytes[:32])),
                     _nonce3("kvcache/k"), _nonce3("kvcache/v"),
                     M.mac_context(key_bytes, "kvcache") if verify else None,
                     _nonce3("kvcache/c"))


def line_flags_from_mask(mask_elems, dtype, n_lines: int) -> jnp.ndarray:
    """Element-level encrypt mask -> per-128B-line flag (any elem encrypted)."""
    epw = 4 // jnp.dtype(dtype).itemsize if jnp.dtype(dtype).itemsize < 4 else 1
    flat = mask_elems.reshape(-1)
    elems_per_line = CL.WORDS_PER_LINE * max(epw, 1)
    pad = n_lines * elems_per_line - flat.shape[0]
    if pad > 0:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), bool)])
    per_line = flat.reshape(n_lines, elems_per_line)
    return jnp.any(per_line, axis=1).astype(jnp.uint32)


# --------------------------------------------------------------------------
# fused (tile-sealed) eligibility
# --------------------------------------------------------------------------

# (parent, name) pairs whose consumption sites are threaded through
# SealedTensor.matmul in models/. Capacity-routed MoE experts (4-D under
# "mlp"), the router, recurrent/SSM projections and the embedding stay on
# the eager path for now (ROADMAP open item).
_FUSED_LEAVES = {("attn", "wq"), ("attn", "wk"), ("attn", "wv"),
                 ("attn", "wo"), ("attn", "wkv_a"), ("attn", "wk_rope"),
                 ("attn", "wk_b"), ("attn", "wv_b"), ("mlp", "wi"),
                 ("mlp", "wg"), ("mlp", "wo"), ("shared", "wi"),
                 ("shared", "wg"), ("shared", "wo"), ("head", "w")}
# Expert stacks (layer, expert, K, N) consumed through SealedTensor.gmm:
# the only leaves sealed with two stack axes, one write counter per
# (layer, expert) slice.
_GMM_LEAVES = {("experts", "wi"), ("experts", "wg"), ("experts", "wo")}
# Leaves stored tile-sealed but decrypted eagerly in-graph (their consumer
# is a gather, not a matmul). The tile layout keeps a (V, D) table as V x D
# words, which a TPU holds unpadded and (un)seals one row of tiles at a
# time; the 34-word line records of the line layout are lane-padded there,
# and (un)sealing a full-width embedding through them takes several GB.
_EAGER_TILE_LEAVES = {("embed", "w")}


class TileGeometry(NamedTuple):
    n_batch: int          # leading stack axes (2 for an expert stack)
    k_ndim: int           # contraction (row) axes
    n_out: int            # trailing output axes
    k: int
    n: int
    bk: int
    bn: int
    fused: bool           # reaches its matmul still sealed; else eager


def _pick_block(dim: int) -> Optional[int]:
    # a tile's pad is 16 keystream-word planes stacked along its rows, so
    # a tile side is a multiple of 16 (``kernels.ref.tile_counters``); a
    # TPU block side is a multiple of 128 or the whole dimension (MLA's
    # 64-wide rotary key projection takes one tile across)
    if dim % 128 == 0:
        return 128
    return dim if dim % 16 == 0 else None


def tile_geometry(path: Tuple[str, ...], shape, dtype,
                  seal: SealConfig) -> Optional[TileGeometry]:
    """The tile layout of the leaf, or None if it takes the line layout.
    ``fused`` says whether it flows still sealed into
    ``SealedTensor.matmul`` or is decrypted eagerly in-graph; every reader
    of that decision (``fused_paths``, ``fused_params``, the dry-run) takes
    it from here. Pure function of shapes, so the dry-run can build
    spec-level sealed trees without allocating."""
    if not seal.fuse_decrypt or seal.mode not in ("counter", "coloe"):
        return None
    keys = {(path[-2] if len(path) >= 2 else "", path[-1]),
            (path[0], path[-1])}
    gmm = bool(keys & _GMM_LEAVES)
    fused = gmm or bool(keys & _FUSED_LEAVES)
    if not fused and not keys & _EAGER_TILE_LEAVES:
        return None
    if jnp.dtype(dtype).itemsize != 4:
        return None                       # payload is the u32 bitcast
    cls = P._classify(path, len(shape))
    if cls is None:
        return None
    batch_axes, row_axes = cls
    nb, nk = len(batch_axes), len(row_axes)
    if nb > (2 if gmm else 1) or batch_axes != tuple(range(nb)) or \
            row_axes != tuple(range(nb, nb + nk)):
        return None
    n_out = len(shape) - nb - nk
    if n_out < 1:
        return None
    k = int(np.prod(shape[nb:nb + nk]))
    n = int(np.prod(shape[nb + nk:]))
    bk, bn = _pick_block(k), _pick_block(n)
    if bk is None or bn is None or bk * bn > M.MAX_WORDS:
        return None                       # a tile's tag covers one tile
    return TileGeometry(nb, nk, n_out, k, n, bk, bn, fused)


# --------------------------------------------------------------------------
# seal
# --------------------------------------------------------------------------

def _seal_lines(eng, seal, leaf, plan, path) -> SealedTensor:
    n_words = -(-leaf.size * leaf.dtype.itemsize // 4)
    n_lines = -(-n_words // CL.WORDS_PER_LINE)
    if plan.mode == "rows":
        mask = P.expand_mask(plan, leaf.shape)
        flags = line_flags_from_mask(mask, leaf.dtype, n_lines)
    else:
        flags = jnp.ones((n_lines,), jnp.uint32)
    sealed = eng.encrypt(leaf, nonce2=_nonce2(path), enc_flags=flags) \
        if seal.mode != "direct" else eng.encrypt(leaf, enc_flags=flags)
    meta = SealMeta(scheme=sealed.scheme, layout="lines",
                    dtype=str(jnp.dtype(leaf.dtype)),
                    nonce=tuple(int(v) for v in sealed.nonce2),
                    shape=tuple(leaf.shape), orig_len=sealed.orig_len)
    # the MAC tweak is always the per-path nonce (the direct scheme's
    # encryption nonce is (0, 0) for every leaf, which must not collapse the
    # tag domains — a line swap across tensors has to be catchable)
    macs = eng.line_macs(sealed, _line_tweak(path)) if seal.verify else None
    return SealedTensor(sealed.payload, sealed.counters, None, None, None,
                        meta, macs=macs)


def _flat_lead(shape, nb: int):
    """The stack axes of a tile-sealed leaf as one: () or (slices,)."""
    return (int(np.prod(shape[:nb])),) if nb else ()


def _seal_tiles(eng, seal, leaf, plan, path, geom) -> SealedTensor:
    nb, nk, n_out, k, n, bk, bn, fused = geom
    nonce3 = _nonce3(path)
    shape = leaf.shape
    lead, flat = shape[:nb], _flat_lead(shape, nb)
    if plan.mask is not None:
        mask = plan.mask.reshape(lead + (k,))
    else:
        mask = jnp.ones(lead + (k,), bool)
    key_arr = jnp.asarray(eng.key_words, jnp.uint32)
    # one write-counter per stack slice (a layer, or a layer's expert):
    # the (key, nonce, counter) triple — hence the OTP — is never reused
    # across layers or experts
    wc = (jnp.arange(flat[0], dtype=jnp.uint32).reshape(lead) if nb
          else jnp.zeros((), jnp.uint32))
    key_c = jnp.broadcast_to(key_arr, lead + (8,))
    ct2d = eng.encrypt_tiles(leaf.reshape(flat + (k, n)), nonce3,
                             mask.reshape(flat + (k,)), wc.reshape(flat),
                             bk, bn)
    payload = ct2d.reshape(shape)
    meta = SealMeta(scheme=eng.name, layout="tiles",
                    dtype=str(jnp.dtype(leaf.dtype)), nonce=nonce3,
                    shape=tuple(shape), n_batch=nb, k_ndim=nk, n_out=n_out,
                    bk=bk, bn=bn, fused=fused)
    macs = (M.tile_tags(eng.mac_ctx, ct2d, mask.reshape(flat + (k,)),
                        wc.reshape(flat), bk, bn, tweak=nonce3
                        ).reshape(lead + (k // bk, n // bn))
            if seal.verify else None)
    return SealedTensor(payload, None, mask, key_c, wc, meta, macs=macs)


def seal_params(params, seal: SealConfig, key_bytes: bytes, *,
                consume: bool = False) -> SealedParams:
    """Seal every leaf of ``params``. With ``consume`` each plaintext leaf
    is deleted as soon as its ciphertext exists, so the device never holds
    the whole model twice and the plaintext does not outlive the seal
    (the caller's ``params`` are dead afterwards)."""
    plans = P.make_plan(params, seal)
    eng = E.make_engine(seal.mode, key_bytes)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    tensors: Dict[str, SealedTensor] = {}
    for keypath, leaf in flat:
        pt = P._path_tuple(keypath)
        path = "/".join(pt)
        plan = plans[path]
        geom = tile_geometry(pt, leaf.shape, leaf.dtype, seal) \
            if eng.supports_fused else None
        if geom is not None:
            tensors[path] = _seal_tiles(eng, seal, leaf, plan, path, geom)
        else:
            tensors[path] = _seal_lines(eng, seal, leaf, plan, path)
        if consume:
            jax.block_until_ready(tensors[path])
            leaf.delete()
    return SealedParams(tensors, plans, treedef, seal)


# --------------------------------------------------------------------------
# unseal
# --------------------------------------------------------------------------

def _unseal_tensor(eng, st: SealedTensor):
    m = st.meta
    if m.layout == "tiles":
        nb = m.n_batch
        k = int(np.prod(m.shape[nb:nb + m.k_ndim]))
        n = int(np.prod(m.shape[nb + m.k_ndim:]))
        flat = _flat_lead(m.shape, nb)
        w = eng.decrypt_tiles(st.payload.reshape(flat + (k, n)), m.nonce,
                              st.row_mask.reshape(flat + (k,)),
                              st.wc.reshape(flat), m.bk, m.bn)
        return w.reshape(m.shape).astype(jnp.dtype(m.dtype))
    buf = E.SealedBuffer(m.scheme, st.payload, st.counters, m.orig_len,
                         m.shape, jnp.dtype(m.dtype), m.nonce)
    return eng.decrypt(buf)


def unseal_params(sp: SealedParams, key_bytes: bytes):
    """Decrypt every leaf; jittable (children traced, metadata static).

    Leaf order comes from ``sp.plans`` (host-side, insertion order ==
    treedef flatten order) with keyed lookups into ``tensors`` — the
    tensors dict itself crosses jit boundaries, where JAX re-sorts dict
    keys lexicographically, which need not match the flatten order.
    """
    eng = E.make_engine(sp.seal.mode, key_bytes)
    flat = [_unseal_tensor(eng, sp.tensors[p]) for p in sp.plans]
    return jax.tree_util.tree_unflatten(sp.treedef, flat)


def fused_params(sp: SealedParams, key_bytes: bytes):
    """The serving view: the matmul leaves pass through STILL SEALED and
    are decrypted in-register by ``kernels.sealed_matmul`` at their
    consumption site; every other leaf decrypts eagerly. (Ordering: see
    ``unseal_params``.)"""
    eng = E.make_engine(sp.seal.mode, key_bytes)
    fused = set(sp.fused_paths())
    with jax.named_scope("weight_decrypt"):
        flat = [sp.tensors[p] if p in fused
                else _unseal_tensor(eng, sp.tensors[p]) for p in sp.plans]
    return jax.tree_util.tree_unflatten(sp.treedef, flat)


def verify_params(sp: SealedParams, key_bytes: bytes):
    """In-graph integrity check of the whole sealed weight image.

    Recomputes every stored tag from the at-rest ciphertext and reduces to
    one scalar bool (True = intact). Constant-time: the reduction shape does
    not depend on the data. Leaves sealed without MACs are skipped, so the
    check is a no-op graph when ``seal.verify`` was off."""
    eng = E.make_engine(sp.seal.mode, key_bytes)
    oks = []
    for path in sp.plans:
        st = sp.tensors[path]
        if st.macs is None:
            continue
        m = st.meta
        if m.layout == "tiles":
            nb = m.n_batch
            k = int(np.prod(m.shape[nb:nb + m.k_ndim]))
            n = int(np.prod(m.shape[nb + m.k_ndim:]))
            flat = _flat_lead(m.shape, nb)
            tags = M.tile_tags(eng.mac_ctx, st.payload.reshape(flat + (k, n)),
                               st.row_mask.reshape(flat + (k,)),
                               st.wc.reshape(flat), m.bk, m.bn,
                               tweak=m.nonce).reshape(st.macs.shape)
        else:
            buf = E.SealedBuffer(m.scheme, st.payload, st.counters,
                                 m.orig_len, m.shape, jnp.dtype(m.dtype),
                                 m.nonce)
            tags = eng.line_macs(buf, _line_tweak(path))
        oks.append(jnp.all(tags == st.macs))
    return jnp.all(jnp.stack(oks)) if oks else jnp.bool_(True)


def n_macs(sp: SealedParams) -> int:
    """Number of stored weight tags (for stats / overhead reporting)."""
    return sum(int(t.macs.size) for t in sp.tensors.values()
               if t.macs is not None)


def sealed_byte_report(sp: SealedParams) -> Dict[str, float]:
    tot = P.plan_totals(sp.plans)
    return {
        "plaintext_bytes": tot["total_bytes"],
        "enc_fraction": tot["enc_fraction"],
        "stored_bytes": sp.stored_bytes(),
        "overhead": sp.stored_bytes() / max(tot["total_bytes"], 1) - 1.0,
        "fused_leaves": len(sp.fused_paths()),
        "plaintext_bytes_per_step": sp.plaintext_bytes_materialized(),
    }
