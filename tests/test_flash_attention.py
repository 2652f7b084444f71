"""Pallas flash-attention kernel vs the naive oracle (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.layers as L
from repro.kernels.ops import flash_attention


@pytest.mark.parametrize("b,s,hq,hkv,dh,win,cap,bq,bkv", [
    (2, 256, 4, 2, 32, 0, 0.0, 64, 64),      # GQA causal
    (1, 512, 8, 1, 32, 128, 50.0, 128, 64),  # MQA + window + softcap
    (2, 256, 6, 6, 16, 0, 0.0, 32, 128),     # MHA, uneven blocks
    (1, 128, 2, 2, 64, 32, 0.0, 32, 32),     # small window
])
def test_flash_matches_naive(b, s, hq, hkv, dh, win, cap, bq, bkv):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, hq, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, dh), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    ref = L._sdpa(q, k, v, L._attn_mask(pos, pos, win), cap, dh ** -0.5)
    out = flash_attention(q, k, v, scale=dh ** -0.5, softcap=cap, window=win,
                          bq=bq, bkv=bkv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_flash_bf16_io():
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 2, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 32), jnp.bfloat16)
    pos = jnp.arange(128, dtype=jnp.int32)
    ref = L._sdpa(q, k, v, L._attn_mask(pos, pos, 0), 0.0, 32 ** -0.5)
    out = flash_attention(q, k, v, scale=32 ** -0.5, bq=64, bkv=64)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=2e-2,
                               atol=2e-2)
