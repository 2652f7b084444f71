"""Engine roundtrips, rewrite (counter-bump) semantics, SE bypass flags,
ColoE layout, and storage accounting — incl. hypothesis property sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import coloe as CL
from repro.core import engine as E

KEY = bytes(range(32))


@pytest.mark.parametrize("mode", ["direct", "counter", "coloe"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(7, 33), (128,), (3, 5, 11)])
def test_roundtrip(mode, dtype, shape):
    eng = E.make_engine(mode, KEY)
    x = jax.random.normal(jax.random.key(0), shape, dtype)
    s = eng.encrypt(x)
    y = eng.decrypt(s)
    assert y.shape == x.shape and y.dtype == x.dtype
    assert bool(jnp.all(x == y))


@pytest.mark.parametrize("mode", ["counter", "coloe"])
def test_rewrite_bumps_counters_changes_ciphertext(mode):
    eng = E.make_engine(mode, KEY)
    x = jax.random.normal(jax.random.key(1), (100,), jnp.float32)
    s0 = eng.encrypt(x)
    s1 = eng.rewrite(s0, x)
    assert bool(jnp.all(eng.decrypt(s1) == x))
    if mode == "coloe":
        d0, _, _ = CL.coloe_unpack(s0.payload)
        d1, _, _ = CL.coloe_unpack(s1.payload)
    else:
        d0, d1 = s0.payload, s1.payload
    # same plaintext re-written -> different ciphertext (no OTP reuse)
    assert not bool(jnp.all(d0 == d1))


def test_direct_is_deterministic_dictionary_attackable():
    """The paper's point about direct encryption: equal plaintext lines ->
    equal ciphertext lines (why SEAL uses counters)."""
    eng = E.make_engine("direct", KEY)
    x = jnp.zeros((64,), jnp.float32)  # two identical 128B lines
    s = eng.encrypt(x)
    assert bool(jnp.all(s.payload[0] == s.payload[1]))
    # counter/coloe do NOT leak equality
    for mode in ["counter", "coloe"]:
        s2 = E.make_engine(mode, KEY).encrypt(x)
        data = s2.payload[:, :CL.WORDS_PER_LINE]
        assert not bool(jnp.all(data[0] == data[1]))


def test_se_bypass_lines_stay_plaintext():
    eng = E.make_engine("coloe", KEY)
    x = jax.random.normal(jax.random.key(2), (96,), jnp.float32)  # 3 lines
    flags = jnp.array([1, 0, 1], jnp.uint32)
    s = eng.encrypt(x, enc_flags=flags)
    data, _, fl = CL.coloe_unpack(s.payload)
    words = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(3, 32)
    assert bool(jnp.all(data[1] == words[1]))        # bypassed: plaintext
    assert not bool(jnp.all(data[0] == words[0]))    # encrypted
    assert bool(jnp.all(eng.decrypt(s) == x))
    assert list(np.asarray(fl)) == [1, 0, 1]


def test_storage_accounting():
    eng = E.make_engine("coloe", KEY)
    x = jnp.zeros((64,), jnp.float32)  # 2 lines
    s = eng.encrypt(x)
    assert s.stored_bytes() == 2 * 34 * 4
    assert s.extra_streams() == 1
    sc = E.make_engine("counter", KEY).encrypt(x)
    assert sc.stored_bytes() == 2 * 32 * 4 + 2 * 8
    assert sc.extra_streams() == 2


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 500), seed=st.integers(0, 2**30),
       mode=st.sampled_from(["direct", "counter", "coloe"]))
def test_roundtrip_property(n, seed, mode):
    eng = E.make_engine(mode, KEY)
    x = jax.random.normal(jax.random.key(seed), (n,), jnp.float32)
    assert bool(jnp.all(eng.decrypt(eng.encrypt(x)) == x))


@settings(max_examples=10, deadline=None)
@given(n=st.integers(32, 300), seed=st.integers(0, 2**30))
def test_ciphertext_not_plaintext(n, seed):
    """Every encrypted line differs from its plaintext (keystream != 0)."""
    eng = E.make_engine("coloe", KEY)
    x = jax.random.normal(jax.random.key(seed), (n,), jnp.float32)
    s = eng.encrypt(x)
    words, _ = CL.pad_to_lines(jax.lax.bitcast_convert_type(x, jnp.uint32))
    data, _, _ = CL.coloe_unpack(s.payload)
    assert not bool(jnp.any(jnp.all(data == words, axis=1)))


def test_coloe_pack_unpack_roundtrip():
    data = jax.random.bits(jax.random.key(0), (5, 32), jnp.uint32)
    ctr = jnp.arange(5, dtype=jnp.uint32)
    fl = jnp.ones((5,), jnp.uint32)
    packed = CL.coloe_pack(data, ctr, fl)
    assert packed.shape == (5, 34)
    d, c, f = CL.coloe_unpack(packed)
    assert bool(jnp.all(d == data)) and bool(jnp.all(c == ctr)) and bool(jnp.all(f == fl))
