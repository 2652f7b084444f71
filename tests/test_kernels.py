"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

KEYW = jnp.asarray(np.frombuffer(bytes(range(32)), np.uint32))
NONCE = jnp.asarray(np.array([7, 11, 13], np.uint32))


@pytest.mark.parametrize("n_blocks,tile", [(128, 128), (512, 256), (1024, 64),
                                           (96, 32), (300, 64)])
def test_chacha_keystream_matches_oracle(n_blocks, tile):
    got = ops.keystream(KEYW, NONCE, n_blocks, tile=tile)
    want = ref.chacha20_keystream_ref(KEYW, NONCE,
                                      jnp.arange(n_blocks, dtype=jnp.uint32))
    assert bool(jnp.all(got == want))


def test_chacha_keystream_counter_offset():
    a = ops.keystream(KEYW, NONCE, 64, counter0=64)
    b = ref.chacha20_keystream_ref(KEYW, NONCE,
                                   jnp.arange(64, 128, dtype=jnp.uint32))
    assert bool(jnp.all(a == b))


@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (32, 64, 128, 32, 32, 64),
    (64, 128, 256, 32, 64, 128),
    (128, 128, 128, 128, 128, 128),
    (16, 256, 64, 16, 64, 32),
])
def test_sealed_matmul_shapes(m, k, n, bm, bk, bn):
    w = jax.random.normal(jax.random.key(0), (k, n), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (m, k), jnp.float32)
    mask = jax.random.bernoulli(jax.random.key(2), 0.5, (k,))
    wct = ops.seal_weights(w, KEYW, NONCE, bk=bk, bn=bn, row_mask=mask)
    y = ops.sealed_matmul(x, wct, mask, KEYW, NONCE, bm=bm, bk=bk, bn=bn)
    y_ref = ref.sealed_matmul_ref(x, wct, KEYW, NONCE, bk, bn, mask)
    y_plain = x @ w
    # kernel accumulates per k-tile; oracle does one dot -> f32 ordering
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_plain),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("k,n,bk,bn,wc", [(32, 64, 16, 32, 0),
                                          (128, 256, 64, 128, 3)])
def test_tile_pad_matches_per_word_derivation(k, n, bk, bn, wc):
    """The pad the row-of-tiles walk XORs in equals the per-word statement
    of the format."""
    ctr, lane = ref.tile_counters(k, n, bk, bn, wc)
    ks = ref.chacha20_keystream_ref(KEYW, NONCE,
                                    jnp.arange(k * n // 16 * (wc + 1),
                                               dtype=jnp.uint32))
    want = np.asarray(ks)[lane, ctr]
    got = np.asarray(ref.tile_xor(jnp.zeros((k, n), jnp.uint32), KEYW,
                                  NONCE, bk, bn, write_counter=wc))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("ratio", [0.0, 0.25, 0.5, 1.0])
def test_sealed_matmul_mask_ratios(ratio):
    k, n = 128, 128
    w = jax.random.normal(jax.random.key(0), (k, n), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (32, k), jnp.float32)
    mask = (jnp.arange(k) < int(ratio * k))
    wct = ops.seal_weights(w, KEYW, NONCE, row_mask=mask)
    # plaintext rows stored verbatim
    wu = jax.lax.bitcast_convert_type(w, jnp.uint32)
    stored_plain = jnp.all(jnp.where(mask[:, None], True, wct == wu))
    assert bool(stored_plain)
    if ratio > 0:
        assert not bool(jnp.all(wct == wu))
    y = ops.sealed_matmul(x, wct, mask, KEYW, NONCE)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-3)


def test_sealed_matmul_write_counter_rotates_otp():
    k, n = 128, 128
    w = jax.random.normal(jax.random.key(0), (k, n), jnp.float32)
    mask = jnp.ones((k,), bool)
    c1 = ops.seal_weights(w, KEYW, NONCE, row_mask=mask, write_counter=1)
    c2 = ops.seal_weights(w, KEYW, NONCE, row_mask=mask, write_counter=2)
    assert not bool(jnp.all(c1 == c2))
    x = jax.random.normal(jax.random.key(1), (16, k), jnp.float32)
    y2 = ops.sealed_matmul(x, c2, mask, KEYW, NONCE, write_counter=2)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-3)


def test_unfused_baseline_matches_fused():
    k, n, m = 128, 256, 32
    w = jax.random.normal(jax.random.key(0), (k, n), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (m, k), jnp.float32)
    mask = jnp.ones((k,), bool)
    wct = ops.seal_weights(w, KEYW, NONCE, row_mask=mask)
    yf = ops.sealed_matmul(x, wct, mask, KEYW, NONCE)
    yu = ops.decrypt_then_matmul(x, wct, mask, KEYW, NONCE)
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yu), rtol=1e-5,
                               atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(mt=st.integers(1, 4), kt=st.integers(1, 4), nt=st.integers(1, 4),
       seed=st.integers(0, 2**30))
def test_sealed_matmul_property(mt, kt, nt, seed):
    bm = bk = bn = 32
    m, k, n = mt * bm, kt * bk, nt * bn
    kk = jax.random.key(seed)
    w = jax.random.normal(kk, (k, n), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(kk, 1), (m, k), jnp.float32)
    mask = jax.random.bernoulli(jax.random.fold_in(kk, 2), 0.5, (k,))
    wct = ops.seal_weights(w, KEYW, NONCE, bk=bk, bn=bn, row_mask=mask)
    y = ops.sealed_matmul(x, wct, mask, KEYW, NONCE, bm=bm, bk=bk, bn=bn)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               rtol=2e-4, atol=2e-3)
