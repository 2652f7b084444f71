"""Pallas TPU kernel: causal flash attention (forward).

§Roofline showed the dominant memory term of train/prefill cells is the
blockwise-attention online-softmax state round-tripping HBM every kv-block
— an artifact of expressing flash attention as an XLA while loop. This
kernel is the fix: the (bq, dh) accumulator and the running max/denominator
live in VMEM scratch across the kv loop; HBM traffic is exactly
q + k + v + out.

Grid: (batch*heads, q_blocks); the causal kv loop runs inside the kernel
body over ``pl.ds`` ref slices of the (t, dh) K/V blocks. GQA is handled by
mapping each q head to its kv head via index_map (no repeated K/V in HBM).

Validated against layers._sdpa in interpret mode (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bkv: int, t: int,
            scale: float, softcap: float, window: int):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale        # (bq, dh)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    nkv_live = (qi * bq + bq + bkv - 1) // bkv        # causal upper bound

    def body(j, carry):
        acc, m_run, d_run = carry
        start = pl.multiple_of(j * bkv, bkv)
        k = k_ref[pl.ds(start, bkv), :].astype(jnp.float32)   # (bkv, dh)
        v = v_ref[pl.ds(start, bkv), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
        mask = k_pos <= q_pos
        if window:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)
        d_new = d_run * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return acc, m_new, d_new

    acc0 = jnp.zeros((bq, q.shape[-1]), jnp.float32)
    m0 = jnp.full((bq, 1), -jnp.inf, jnp.float32)
    d0 = jnp.zeros((bq, 1), jnp.float32)
    lo = 0
    if window:
        lo = jnp.maximum(qi * bq - window + 1, 0) // bkv
    acc, m_run, d_run = jax.lax.fori_loop(lo, nkv_live, body, (acc0, m0, d0))
    o_ref[...] = (acc / jnp.maximum(d_run, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "window",
                                             "bq", "bkv", "interpret"))
def flash_attention(q, k, v, *, scale: float, softcap: float = 0.0,
                    window: int = 0, bq: int = 128, bkv: int = 128,
                    interpret: bool):
    """q: (b, s, hq, dh); k, v: (b, t, hkv, dh); causal. Returns (b, s, hq, dh).

    The online-softmax state stays in VMEM for the whole kv loop — the HBM
    traffic is q+k+v+out, vs O(s*t) for score-materializing attention and
    O(nkv * state) for the XLA-loop blockwise version.
    """
    b, s, hq, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    assert s % bq == 0 and t % bkv == 0, (s, t, bq, bkv)

    # layout: fold batch*heads into the grid's first axis
    qf = jnp.moveaxis(q, 2, 1).reshape(b * hq, s, dh)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * hkv, t, dh)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * hkv, t, dh)

    kernel = functools.partial(_kernel, bq=bq, bkv=bkv, t=t, scale=scale,
                               softcap=softcap, window=window)
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, s // bq),
        in_specs=[
            pl.BlockSpec((None, bq, dh), lambda h, i: (h, i, 0)),
            # GQA: q head h reads kv head h' = (h % hq) // g of its batch
            pl.BlockSpec((None, t, dh),
                         lambda h, i: ((h // hq) * hkv + (h % hq) // g, 0, 0)),
            pl.BlockSpec((None, t, dh),
                         lambda h, i: ((h // hq) * hkv + (h % hq) // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, dh), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, s, dh), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return jnp.moveaxis(out.reshape(b, hq, s, dh), 1, 2)
