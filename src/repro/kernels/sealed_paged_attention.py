"""Pallas TPU kernel: attention over the sealed paged KV cache, read in place.

The decode tick and the chunked-prefill step attend every row's queries over
the row's cache prefix ``[0, lengths[r])``, which lives in the stacked pool
as ciphertext blocks reached through the row's block table. This kernel
reads those blocks straight from the stacked pool by (layer, block), only as
far as each row's length, and does everything else in VMEM:

* **Walk.** Grid (B,), tables and lengths scalar-prefetched. Row r walks
  its ``ceil(lengths[r] / bs)`` resident blocks in groups of 8, DMAing each
  block once, double-buffered (group g+1 streams in while group g
  computes). Nothing past the last resident block is fetched or computed.
* **Verify.** Each resident block's Carter–Wegman hash (``mac.uhash``) is
  recomputed over the ciphertext in VMEM and compared with its stored tag,
  whose Wegman–Carter pad the caller removed (``expect``, from the trusted
  write counters). A mismatch marks only the owning row.
* **Unseal.** A K/V block is a (rows, 128) u32 tile stack, sealed in the
  plane order of ``ref.cache_block_otp``: word w of ChaCha block c pads
  element ``w * cpb + c``. So the keystream of a block is 16 (cpb/128, 128)
  planes, one per state word, stacked along rows — generated in registers
  (``chacha20._double_round``, ten times) and XORed with no relayout, as
  ``sealed_matmul.tile_plaintext`` does.
* **Attend.** A token is ``rows / bs`` rows of 128 words, each row holding
  whole kv heads. The group's 8 blocks stack into one (8 * rows, 128) key
  matrix. Each query row carries its head's words in its head's lanes and
  zeros elsewhere, and is masked to the key rows of its head's quarter, so
  one ``q @ K^T`` scores every head and the q heads of a kv head share one
  K/V read (GQA without a repeat). A bf16 word holds elements (2i, 2i+1),
  the even one in the low half: keys and values unpack into an even and an
  odd part, and the queries arrive split the same way. Online softmax in
  f32; the output is left unnormalised, with its running max and
  denominator, for the caller to merge with the fresh tokens' own keys.

A plaintext pool takes the same kernel with the XOR and the MAC left out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.mac import _mul_mod, combine_halves
from repro.kernels.chacha20 import _CONST, _double_round

PAGES = 8                   # pool blocks per group
MASKED = -1e30              # score of a key outside the row's prefix
_LANES = 128


def _splat(x, shape):
    """A u32 ``shape`` array of the i32 scalar ``x`` (Mosaic bitcasts
    vectors, not scalars)."""
    return lax.bitcast_convert_type(jnp.full(shape, x, jnp.int32), jnp.uint32)


def _sum(x):
    """(R, L) u32 -> (1, 1): the sum, which stays below 2^31 (Mosaic
    reduces signed integers only)."""
    s = lax.bitcast_convert_type(x, jnp.int32)
    s = jnp.sum(jnp.sum(s, axis=0, keepdims=True), axis=1, keepdims=True)
    return lax.bitcast_convert_type(s, jnp.uint32)


def _make_kernel(*, b, mb, bs, rows, lanes, packed, kv_dtype, scale, softcap,
                 sealed, verify, interpret):
    wpb = rows * lanes
    cpb = wpb // 16                 # ChaCha blocks per pool block
    plane = (max(cpb // lanes, 1), min(cpb, lanes))
    rpt = rows // bs                # rows per token
    n_keys = PAGES * rows
    dt = jnp.dtype(kv_dtype)

    def kernel(lens_ref, tbl_ref, meta_ref, *refs):
        refs = list(refs)
        wc_ref = refs.pop(0) if sealed else None
        exp_ref = refs.pop(0) if verify else None
        q_ref, quarter_ref, k_hbm, v_hbm = refs[:4]
        refs = refs[4:]
        hk_ref = refs.pop(0) if verify else None
        acc_ref, m_ref, l_ref, bad_ref, buf, sem = refs[:6]

        row = pl.program_id(0)
        length = lens_ref[row]
        nblk = (length + bs - 1) // bs
        ngrp = (nblk + PAGES - 1) // PAGES
        layer = meta_ref[0]

        def entry(g, p):
            return row * mb + jnp.minimum(g * PAGES + p, mb - 1)

        def copies(g, slot):
            for p in range(PAGES):
                blk = tbl_ref[entry(g, p)]
                for si, hbm in enumerate((k_hbm, v_hbm)):
                    yield g * PAGES + p < nblk, pltpu.make_async_copy(
                        hbm.at[layer, blk], buf.at[si, slot, p],
                        sem.at[si, slot])

        def start(g, slot):
            for live, cp in copies(g, slot):
                pl.when(live)(cp.start)

        def wait(g, slot):
            for live, cp in copies(g, slot):
                pl.when(live)(cp.wait)

        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        bad_ref[...] = jnp.zeros(bad_ref.shape, jnp.int32)

        @pl.when(ngrp > 0)
        def _():
            start(0, 0)

        def check(words, si, g, p):
            """Compare block p's hash with its tag; mark the row on a
            mismatch."""
            m16 = jnp.uint32(0xFFFF)
            t0 = _mul_mod(hk_ref[0], words & m16)
            t1 = _mul_mod(hk_ref[1], words >> 16)
            uh = combine_halves(_sum((t0 & m16) + (t1 & m16)),
                                _sum((t0 >> 16) + (t1 >> 16)))
            want = _splat(exp_ref[si * b * mb + entry(g, p)], (1, 1))
            bad = (uh != want).astype(jnp.int32)
            bad_ref[...] |= jnp.broadcast_to(bad, bad_ref.shape)

        def pad(si, g, p):
            """Block p's keystream, (rows, lanes) in plane order."""
            meta = lambda i: _splat(meta_ref[i], plane)
            init = [jnp.full(plane, _CONST[i], jnp.uint32) for i in range(4)]
            init += [meta(2 + i) for i in range(8)]
            ctr = (lax.broadcasted_iota(jnp.uint32, plane, 0)
                   * jnp.uint32(plane[1])
                   + lax.broadcasted_iota(jnp.uint32, plane, 1))
            init.append(_splat(tbl_ref[entry(g, p)], plane) * jnp.uint32(cpb)
                        + ctr)
            init += [meta(10 + 3 * si) ^ meta(1),
                     meta(11 + 3 * si) ^ _splat(wc_ref[entry(g, p)], plane),
                     meta(12 + 3 * si)]
            # unrolled on the chip, where it makes the read a third
            # faster; rolled in interpret mode, where unrolled rounds cost
            # every program that holds the kernel seconds of CPU compile
            x16 = lax.fori_loop(0, 10, lambda _, x: tuple(_double_round(
                list(x))), tuple(init), unroll=not interpret)
            ks = jnp.concatenate([x16[i] + init[i] for i in range(16)],
                                 axis=0)
            return ks.reshape(rows, lanes)

        def read_block(si, slot, g, p):
            words = buf[si, slot, p]
            if verify:
                check(words, si, g, p)
            if sealed:
                words = words ^ pad(si, g, p)
            buf[si, slot, p] = words

        def unpack(words):
            if not packed:
                return [lax.bitcast_convert_type(words, dt)]
            f32 = lambda x: lax.bitcast_convert_type(x, jnp.float32)
            return [f32(words << 16).astype(dt),
                    f32(words & jnp.uint32(0xFFFF0000)).astype(dt)]

        def compute(g, slot):
            if sealed:
                def read(i, carry):
                    si, p = i // PAGES, i % PAGES
                    pl.when(g * PAGES + p < nblk)(
                        lambda: read_block(si, slot, g, p))
                    return carry

                lax.fori_loop(0, 2 * PAGES, read, 0)
            # key row r: block r // rows, token (r % rows) // rpt of it,
            # holding the heads of quarter r % rpt
            kr = lax.broadcasted_iota(jnp.int32, (1, n_keys), 1)
            pos = (g * PAGES + kr // rows) * bs + (kr % rows) // rpt
            valid = (pos < length) & (kr % rpt == quarter_ref[...])
            vr = lax.broadcasted_iota(jnp.int32, (n_keys, 1), 0)
            vvalid = (g * PAGES + vr // rows) * bs + (vr % rows) // rpt < length
            kparts = unpack(buf[0, slot].reshape(n_keys, lanes))
            s = sum(lax.dot_general(q_ref[i], kp, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                    for i, kp in enumerate(kparts))
            s = s * scale
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(valid, s, MASKED)
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[...] = jnp.broadcast_to(
                alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
                l_ref.shape)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            p = p.astype(dt)
            vparts = unpack(buf[1, slot].reshape(n_keys, lanes))
            for i, vp in enumerate(vparts):
                vp = jnp.where(vvalid, vp, jnp.zeros_like(vp))
                acc_ref[i] = acc_ref[i] * alpha + jnp.dot(
                    p, vp, preferred_element_type=jnp.float32)

        def body(g, carry):
            slot = g % 2
            wait(g, slot)

            @pl.when(g + 1 < ngrp)
            def _():
                start(g + 1, 1 - slot)

            compute(g, slot)
            return carry

        lax.fori_loop(0, ngrp, body, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "block_size", "kv_dtype", "scale", "softcap", "interpret"))
def sealed_paged_attention(q, quarter, k_pool, v_pool, lengths, tables, meta,
                           wcb, expect, hash_keys, *, block_size: int,
                           kv_dtype: str, scale: float, softcap: float,
                           interpret: bool):
    """Attention of every row's queries over its sealed pool prefix.

    q: (B, parts, R, lanes) in ``kv_dtype``: R query rows (query x head)
    per row, each holding its head's even (part 0) and odd (part 1) words
    — or its whole words (one part, a 4-byte kv dtype) — in the lanes its
    kv head takes in a pool row, zeros elsewhere. quarter: (R, 1) i32, the
    row of a token (0 .. rows/bs - 1) that holds each query row's kv head.
    k_pool / v_pool: the stacked pools (n, NB, rows, lanes) u32, read in
    place. lengths (B,) i32; tables (B, MB) i32; meta (16,) i32: layer
    index, layer id, key words, K nonce, V nonce. wcb (B, MB) u32: the
    write counters of the tables' blocks, or None for a plaintext pool;
    expect (2, B, MB) u32: the K and V tags with their pads removed, and
    hash_keys (2, rows, lanes) u32: the hash keys of each word's low and
    high half, or None without verification.

    Returns (acc (B, parts, R, lanes) f32 unnormalised, m (B, R) f32
    running max, l (B, R) f32 denominator, bad (B,) bool: a resident block
    of the row failed its MAC).
    """
    b, parts, r, lanes = q.shape
    mb = tables.shape[1]
    rows = k_pool.shape[-2]
    assert k_pool.shape[-1] == lanes and rows % block_size == 0, \
        (k_pool.shape, q.shape, block_size)
    sealed, verify = wcb is not None, expect is not None
    assert sealed or not verify
    kernel = _make_kernel(b=b, mb=mb, bs=block_size, rows=rows, lanes=lanes,
                          packed=parts == 2, kv_dtype=kv_dtype, scale=scale,
                          softcap=softcap, sealed=sealed, verify=verify,
                          interpret=interpret)
    i32 = lambda x: lax.bitcast_convert_type(
        jnp.asarray(x, jnp.uint32), jnp.int32).reshape(-1)
    prefetch = [lengths.astype(jnp.int32),
                tables.astype(jnp.int32).reshape(-1), meta.astype(jnp.int32)]
    if sealed:
        prefetch.append(i32(wcb))
    if verify:
        prefetch.append(i32(expect))
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    per_row = lambda *shape: pl.BlockSpec(
        (None,) + shape, lambda i, *_: (i,) + (0,) * len(shape))
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    in_specs = [per_row(parts, r, lanes), whole(r, 1), any_, any_]
    inputs = [q, quarter, k_pool, v_pool]
    if verify:
        in_specs.append(whole(2, rows, lanes))
        inputs.append(hash_keys)
    out_shape = [jax.ShapeDtypeStruct((b, parts, r, lanes), jnp.float32),
                 jax.ShapeDtypeStruct((b, r, _LANES), jnp.float32),
                 jax.ShapeDtypeStruct((b, r, _LANES), jnp.float32),
                 jax.ShapeDtypeStruct((b, PAGES, _LANES), jnp.int32)]
    out_specs = [per_row(parts, r, lanes), per_row(r, _LANES),
                 per_row(r, _LANES), per_row(PAGES, _LANES)]
    acc, m, l, bad = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(b,),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((2, 2, PAGES, rows, lanes), jnp.uint32),
                pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="sealed_paged_attention",
    )(*prefetch, *inputs)
    return acc, m[..., 0], l[..., 0], jnp.any(bad != 0, axis=(1, 2))
