"""Sealed-decode dry-run: the paper's own scenario measured on compiled
512/256-chip artifacts (EXPERIMENTS.md §Perf hillclimb #1).

The serve step decrypts the HBM-resident ciphertext weights in-graph every
step. Variants map to the paper's schemes:

  baseline   — plaintext weights (paper's insecure Baseline)
  counter    — counter-mode, separate counter tables, FULL encryption
  coloe      — ColoE (counters inline), FULL encryption
  coloe_se   — ColoE + Smart Encryption at ratio r with LAYOUT SPLITTING:
               ciphertext rows stored contiguously so the keystream is
               generated for exactly r of the bytes (beyond-paper: the
               paper's memory controller sees interleaved lines; we
               re-layout at rest). Plaintext rows skip the engine entirely.
  coloe_fused — ColoE + SE where matmul-shaped leaves take the tile-sealed
               ``SealedTensor`` layout and flow STILL SEALED into the fused
               decrypt-in-matmul Pallas kernel; only the small leaf
               fraction decrypts eagerly. ``plaintext_bytes_materialized``
               in the output records is the per-step plaintext traffic each
               variant pays — for coloe_fused it drops to the non-matmul
               fraction.

Masks are synthesized structurally (first ceil(r*rows) rows of each SE
leaf), so the whole pipeline works from ShapeDtypeStructs — no 2.5B-param
allocation.
"""
import os
# a host-device compile tool: it must never take a TPU, so the CPU platform
# is pinned before JAX is imported
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import SHAPES, SealConfig
from repro.configs import get_config, get_reduced
from repro.core import cipher as C
from repro.core import coloe as CL
from repro.core import engine as E
from repro.core import plan as PL
from repro.core import sealed_store as SS
from repro.core.sealed_tensor import SealMeta, SealedTensor
from repro.launch import hlo_stats
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.sharding import rules
from repro.sharding.api import use_mesh

KEYW = np.frombuffer(bytes(range(32)), np.uint32)


def _leaf_lines(leaf) -> int:
    words = -(-leaf.size * leaf.dtype.itemsize // 4)
    return -(-words // CL.WORDS_PER_LINE)


def synthetic_masks(pspec, seal: SealConfig):
    """Structural SE masks (first ceil(r*rows) rows) per leaf; None=full."""
    plans = {}
    flat = jax.tree_util.tree_flatten_with_path(pspec)[0]
    for kp, leaf in flat:
        path = "/".join(PL._path_tuple(kp))
        cls = PL._classify(PL._path_tuple(kp), leaf.ndim)
        boundary = path.split("/")[0] in ("embed", "head")
        if cls is None or seal.smart_ratio >= 1.0 or boundary:
            plans[path] = None          # fully encrypted
        else:
            plans[path] = seal.smart_ratio
    return plans


def lower_sealed_decode(arch: str, shape_name: str, variant: str,
                        ratio: float = 0.5, multi_pod: bool = False,
                        reduced: bool = False):
    """Trace and lower one sealed-decode variant's step. Returns the
    lowered step and the record of what it stores and decrypts."""
    known = ("baseline", "counter", "coloe", "coloe_se", "coloe_fused")
    if variant not in known:
        raise ValueError(f"unknown variant {variant!r}; known: {known}")
    cfg = get_reduced(arch) if reduced else get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    pspec = T.param_spec(cfg)
    p_ps = rules.param_pspecs(cfg, mesh)
    specs = input_specs(cfg, shape)
    c_sh = rules.to_named(mesh, rules.cache_pspecs(
        cfg, mesh, shape.global_batch, shape.seq_len))
    b_sh = rules.to_named(mesh, rules.batch_pspecs(cfg, mesh, "decode"))
    dpsz = np.prod([s for a, s in zip(mesh.axis_names, mesh.devices.shape)
                    if a in ("pod", "data")])
    b_sh = jax.tree.map(
        lambda s, sh: NamedSharding(mesh, P(*([None] * len(s.shape))))
        if s.shape[0] % dpsz else sh, specs["batch"], b_sh)

    flat, treedef = jax.tree_util.tree_flatten_with_path(pspec)
    seal = SealConfig(mode="coloe", smart_ratio=ratio)
    ratios = synthetic_masks(pspec, seal)
    p_ps_flat = {"/".join(PL._path_tuple(kp)): ps for kp, ps in
                 jax.tree_util.tree_flatten_with_path(p_ps)[0]}

    # --- build ciphertext buffer SPECS + the in-graph decrypt ---
    buf_specs, buf_shard, meta, tile_metas = {}, {}, {}, {}
    for kp, leaf in flat:
        pt_path = PL._path_tuple(kp)
        path = "/".join(pt_path)
        lines = _leaf_lines(leaf)
        r = ratios[path]
        geom = (SS.tile_geometry(pt_path, leaf.shape, leaf.dtype, seal)
                if variant == "coloe_fused" else None)
        if geom is not None:
            # tile-sealed SealedTensor leaf: ciphertext payload in the
            # weight's own shape (sharded exactly like the plaintext param
            # would be), SE row mask, per-slice write counters, key words.
            # A fused leaf reaches its matmul still sealed; any other is
            # decrypted in-graph, as ``SS.fused_params`` does, and pays its
            # plaintext bytes every step.
            nb, nk, n_out, k, n, bk, bn, fused = geom
            lead = leaf.shape[:nb]
            d = {"ct": jax.ShapeDtypeStruct(leaf.shape, jnp.uint32),
                 "mask": jax.ShapeDtypeStruct(lead + (k,), jnp.bool_),
                 "wc": jax.ShapeDtypeStruct(lead, jnp.uint32),
                 "key": jax.ShapeDtypeStruct(lead + (8,), jnp.uint32)}
            buf_specs[path] = d
            buf_shard[path] = {
                "ct": NamedSharding(mesh, p_ps_flat[path]),
                "mask": NamedSharding(mesh, P(*([None] * (nb + 1)))),
                "wc": NamedSharding(mesh, P(*([None] * nb))),
                "key": NamedSharding(mesh, P(*([None] * (nb + 1))))}
            tile_metas[path] = SealMeta(
                scheme="coloe", layout="tiles",
                dtype=str(jnp.dtype(leaf.dtype)),
                nonce=SS._nonce3(path), shape=tuple(leaf.shape),
                n_batch=nb, k_ndim=nk, n_out=n_out, bk=bk, bn=bn,
                fused=fused)
            # tile layout: no per-line counter area, SE mask rides as 1B/row
            stored_leaf = leaf.size * 4 + int(np.prod(lead + (k,)))
            pt_leaf = 0 if fused else leaf.size * jnp.dtype(leaf.dtype).itemsize
            meta[path] = (leaf.shape, leaf.dtype, lines, lines,
                          stored_leaf, pt_leaf)
            continue
        if variant == "baseline":
            enc_lines, plain_lines, streams = 0, lines, 1
        elif variant in ("counter", "coloe", "coloe_fused"):
            enc_lines, plain_lines = lines, 0
            streams = 2 if variant == "counter" else 1
        else:                            # coloe_se: layout-split
            enc_lines = lines if r is None else -(-int(lines * r) // 1)
            plain_lines = lines - enc_lines
            streams = 1
        words_per = (CL.COLOE_LINE_WORDS
                     if variant in ("coloe", "coloe_se", "coloe_fused")
                     else CL.WORDS_PER_LINE)
        d = {}
        if enc_lines:
            d["ct"] = jax.ShapeDtypeStruct((enc_lines, words_per), jnp.uint32)
        if plain_lines:
            d["pt"] = jax.ShapeDtypeStruct((plain_lines, CL.WORDS_PER_LINE),
                                           jnp.uint32)
        if variant == "counter" and enc_lines:
            d["ctr"] = jax.ShapeDtypeStruct((enc_lines,), jnp.uint32)
        buf_specs[path] = d
        # each device holds its slice of the ciphertext image (lines over
        # `data`); decryption is local, the plaintext gathers afterwards —
        # exactly the per-chip decrypt-on-use deployment.
        dsz = dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)
        buf_shard[path] = {
            k: NamedSharding(mesh, P("data" if v.shape[0] % dsz == 0 else None,
                                     *([None] * (v.ndim - 1))))
            for k, v in d.items()}
        stored_leaf = (enc_lines * words_per + plain_lines * CL.WORDS_PER_LINE
                       + (enc_lines * 2 if variant == "counter" else 0)) * 4
        pt_leaf = (0 if variant == "baseline"
                   else leaf.size * jnp.dtype(leaf.dtype).itemsize)
        meta[path] = (leaf.shape, leaf.dtype, lines, enc_lines,
                      stored_leaf, pt_leaf)

    key_words = jnp.asarray(KEYW)
    tile_eng = E.make_engine("coloe", KEYW.tobytes())

    def unseal(buffers):
        leaves = []
        for kp, leaf in flat:
            path = "/".join(PL._path_tuple(kp))
            if path in tile_metas:
                b = buffers[path]
                st = SealedTensor(b["ct"], None, b["mask"], b["key"],
                                  b["wc"], tile_metas[path])
                leaves.append(st if st.meta.fused
                              else SS._unseal_tensor(tile_eng, st))
                continue
            shape_, dtype_, lines, enc_lines = meta[path][:4]
            parts = []
            b = buffers[path]
            if enc_lines:
                ct = b["ct"]
                if variant in ("coloe", "coloe_se", "coloe_fused"):
                    data, wc, _ = CL.coloe_unpack(ct)
                else:
                    data, wc = ct, b["ctr"]
                addr = jnp.arange(enc_lines, dtype=jnp.uint32)
                from repro.core.engine import _line_otp
                otp = _line_otp(key_words, addr, wc & jnp.uint32(0x7FFFFFFF),
                                (1, 2))
                parts.append(data ^ otp)
            if lines - enc_lines:
                parts.append(b["pt"])
            words = jnp.concatenate(parts, 0).reshape(-1) if parts else None
            from repro.core.engine import words_to_tensor
            n_words = -(-int(np.prod(shape_)) * jnp.dtype(dtype_).itemsize // 4)
            leaves.append(words_to_tensor(words[:n_words], shape_, dtype_))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def step(buffers, cache, batch, pos):
        params = unseal(buffers) if variant != "baseline" else \
            jax.tree_util.tree_unflatten(
                treedef, [words_to_plain(buffers, kp) for kp, _ in flat])
        return T.decode_step(cfg, params, cache, batch, pos)

    def words_to_plain(buffers, kp):
        from repro.core.engine import words_to_tensor
        path = "/".join(PL._path_tuple(kp))
        shape_, dtype_, lines, _ = meta[path][:4]
        n_words = -(-int(np.prod(shape_)) * jnp.dtype(dtype_).itemsize // 4)
        return words_to_tensor(buffers[path]["pt"].reshape(-1)[:n_words],
                               shape_, dtype_)

    # KV-cache plaintext traffic: every decode step streams the whole cache
    # through attention. This launcher's variants all keep the cache
    # plaintext (they measure weight sealing); the paged serving path
    # (serve/engine.py, seal_cache=True) seals it and drives this term to 0.
    kv_bytes = sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                   for s in jax.tree.leaves(specs["cache"]))

    with use_mesh(mesh, rules.arch_rules(cfg, mesh)):
        jf = jax.jit(step, in_shardings=(buf_shard, c_sh, b_sh,
                                         NamedSharding(mesh, P())),
                     donate_argnums=(1,))
        lowered = jf.lower(buf_specs, specs["cache"], specs["batch"],
                           specs["pos"])
    return lowered, {
        "arch": arch, "shape": shape_name, "variant": variant, "ratio": ratio,
        "stored_param_bytes_global": sum(m[4] for m in meta.values()),
        "plaintext_bytes_materialized_per_step": sum(m[5] for m in
                                                     meta.values()),
        "kv_cache_plaintext_bytes_per_step": kv_bytes,
        "fused_matmul_leaves": sum(m.fused for m in tile_metas.values()),
    }


def sealed_decode_variant(arch: str, shape_name: str, variant: str,
                          ratio: float = 0.5, multi_pod: bool = False,
                          reduced: bool = False):
    """Lower+compile one sealed-decode variant; return parser stats."""
    t0 = time.time()
    lowered, rec = lower_sealed_decode(arch, shape_name, variant, ratio,
                                       multi_pod, reduced)
    compiled = lowered.compile()
    stats = hlo_stats.module_totals(compiled.as_text())
    ma = compiled.memory_analysis()
    return {
        **rec,
        "compile_s": round(time.time() - t0, 1),
        "flops_per_device": stats["flops"],
        "bytes_per_device": stats["bytes"],
        "collective_bytes_per_device": sum(stats["collectives"].values()),
        "temp_gib": ma.temp_size_in_bytes / 2**30,
        "arg_gib": ma.argument_size_in_bytes / 2**30,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_3_2b")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--variant", default="all")
    ap.add_argument("--ratio", type=float, default=0.5)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced config (CI smoke)")
    ap.add_argument("--out", default="results/sealed_decode.json")
    args = ap.parse_args()
    variants = (["baseline", "counter", "coloe", "coloe_se", "coloe_fused"]
                if args.variant == "all" else [args.variant])
    out = []
    for v in variants:
        rec = sealed_decode_variant(args.arch, args.shape, v, args.ratio,
                                    reduced=args.reduced)
        print(json.dumps(rec))
        out.append(rec)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
