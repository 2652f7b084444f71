"""SE criticality ranking + EncryptionPlan invariants (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import SealConfig
from repro.configs import get_reduced
from repro.core import criticality as CR
from repro.core import plan as P
from repro.core.sealed_store import seal_params, unseal_params
from repro.models import transformer as T


def test_row_importance_conv():
    w = jnp.zeros((3, 3, 4, 8)).at[:, :, 2, :].set(10.0).at[:, :, 0, :].set(1.0)
    imp = CR.conv_row_importance(w)
    assert int(jnp.argmax(imp)) == 2
    assert imp.shape == (4,)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 64), ratio=st.floats(0.0, 1.0), seed=st.integers(0, 2**30))
def test_mask_selects_exact_topk(n, ratio, seed):
    imp = jax.random.normal(jax.random.key(seed), (n,)) ** 2
    m = CR.encryption_mask(imp, ratio)
    k = int(np.ceil(ratio * n))
    assert int(jnp.sum(m)) == k
    if 0 < k < n:
        # selected rows are the top-k by importance
        thresh = jnp.sort(imp)[n - k]
        assert bool(jnp.all(imp[m] >= jnp.min(imp[m])))
        assert float(jnp.min(imp[m])) >= float(jnp.max(jnp.where(m, -jnp.inf, imp))) - 1e-6


@settings(max_examples=10, deadline=None)
@given(r1=st.floats(0.1, 0.5), r2=st.floats(0.5, 1.0), seed=st.integers(0, 100))
def test_mask_monotone_in_ratio(r1, r2, seed):
    imp = jax.random.normal(jax.random.key(seed), (32,)) ** 2
    m1, m2 = CR.encryption_mask(imp, r1), CR.encryption_mask(imp, r2)
    assert bool(jnp.all(m2 | ~m1))   # m1 subset of m2


def test_plan_classification_and_fractions():
    cfg = get_reduced("internlm2_1_8b").with_(num_layers=8)
    params = T.init_params(cfg, jax.random.key(0))
    plans = P.make_plan(params, SealConfig(mode="coloe", smart_ratio=0.5))
    rows = [p for p in plans.values() if p.mode == "rows"]
    full = [p for p in plans.values() if p.mode == "full"]
    assert rows and full
    # embedding/head always fully protected
    assert plans["embed/w"].mode == "full"
    # boundary superblocks fully encrypted; middle ones at ~ratio
    for p in rows:
        m = p.mask
        assert bool(jnp.all(m[0])) and bool(jnp.all(m[-1]))
        mid = float(jnp.mean(m[1:-1].astype(jnp.float32)))
        assert 0.45 <= mid <= 0.55


def test_plan_ratio_controls_bytes():
    cfg = get_reduced("internlm2_1_8b").with_(num_layers=8)
    params = T.init_params(cfg, jax.random.key(0))
    fr = []
    for r in [0.1, 0.5, 0.9]:
        plans = P.make_plan(params, SealConfig(mode="coloe", smart_ratio=r))
        fr.append(P.plan_totals(plans)["enc_fraction"])
    assert fr[0] < fr[1] < fr[2]


def test_expand_mask_shapes():
    cfg = get_reduced("qwen3_moe_30b_a3b").with_(num_layers=4)
    params = T.init_params(cfg, jax.random.key(0))
    plans = P.make_plan(params, SealConfig(mode="coloe", smart_ratio=0.5))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for kp, leaf in flat:
        path = "/".join(P._path_tuple(kp))
        m = P.expand_mask(plans[path], leaf.shape)
        assert m.shape == leaf.shape


@pytest.mark.parametrize("mode", ["coloe", "counter", "direct"])
def test_sealed_store_roundtrip(mode):
    cfg = get_reduced("gemma2_2b")
    params = T.init_params(cfg, jax.random.key(0))
    sp = seal_params(params, SealConfig(mode=mode, smart_ratio=0.5), bytes(range(32)))
    back = unseal_params(sp, bytes(range(32)))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert bool(jnp.all(a == b))


def test_sealed_store_jit_decrypt():
    """unseal inside jit (the serving path)."""
    cfg = get_reduced("internlm2_1_8b")
    params = T.init_params(cfg, jax.random.key(0))
    sp = seal_params(params, SealConfig(mode="coloe", smart_ratio=0.5),
                     bytes(range(32)))

    @jax.jit
    def f(tensors):
        from repro.core.sealed_store import SealedParams
        sp2 = SealedParams(tensors, sp.plans, sp.treedef, sp.seal)
        p = unseal_params(sp2, bytes(range(32)))
        return p["embed"]["w"][:4, :4]

    out = f(sp.tensors)
    assert bool(jnp.all(out == params["embed"]["w"][:4, :4]))
