"""Moonlight-16B-A3B's block (DeepSeek-V3: MLA, sigmoid-routed dropless
experts at one chip's share, shared experts, a leading dense layer) at the
reduced size, on the CPU, against the plain reference
``bench/refs/mla_moe.py`` and the identities the serving path relies on."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moonlight_helpers import (KEY, assert_close_to_reference, reference,
                               served_logits)
from repro.config import SealConfig
from repro.configs import get_config, get_reduced
from repro.core import sealed_store as SS
from repro.kernels import ops
from repro.kernels import ref as KR
from repro.models import layers as L
from repro.models import paged as PG
from repro.models import cache as MC
from repro.models import transformer as T
from repro.serve.engine import ServeEngine

ARCH = "moonlight_16b_a3b"


def _toks(cfg, b, s, seed):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (b, s)), jnp.int32)


def test_configs_state_the_published_model_and_the_chip_share():
    full, share = get_config(ARCH), get_config("moonlight_16b_a3b_ep8")
    assert (full.num_layers, full.moe.num_experts, full.moe.held) == (27, 64, 64)
    assert (share.num_layers, share.moe.num_experts, share.moe.held) == (16, 64, 8)
    assert share.first_dense == 1 and share.mla.latent == 576
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(T.param_spec(share)))
    assert 2.25e9 < n < 2.27e9, n        # 2.260 B held on the chip
    assert 15.5e9 < full.param_count() < 16.5e9
    assert share.moe.router == "sigmoid_bias"
    assert get_config("qwen3_moe_30b_a3b").moe.router == "softmax"
    with pytest.raises(ValueError, match="router"):
        dataclasses.replace(share.moe, router="sigmoid")


def test_reference_matches_the_program_at_f32():
    """The absorbed attention over the latent and the grouped experts give
    the un-absorbed reference's logits at f32, and the float8 control
    does not."""
    cfg = get_reduced(ARCH).with_(dtype="float32")
    key = jax.random.key(5)
    params = T.init_params(cfg, key)
    toks = _toks(cfg, 2, 24, 1)
    with jax.default_matmul_precision("highest"):
        x = T._embed(cfg, params, {"tokens": toks})
        x, _, _ = T._run_layers(cfg, params, x, jnp.arange(24), "train",
                                None, "none")
        prog = T._unembed(cfg, params,
                          L.apply_norm(cfg, params["final_norm"], x))
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    ref = reference(cfg, key, toks, pos)
    assert float(jnp.max(jnp.abs(prog - ref))) < 1e-4
    ctl = reference(cfg, key, toks, pos, quant="fp8")
    assert float(jnp.median(jnp.abs(ctl - ref))) > 1e-2


@pytest.mark.parametrize("seed", [3, 4, 11])
def test_served_logits_match_the_reference_plaintext(seed):
    """(a) Chunked prefill, then decode through the paged latent cache."""
    cfg = get_reduced(ARCH)
    key = jax.random.key(seed)
    params = T.init_params(cfg, key)
    toks = _toks(cfg, 2, 60, seed)
    served, ok = served_logits(cfg, params, toks, plen=20, chunk=8)
    pos = jnp.broadcast_to(jnp.arange(19, 59), (2, 40))
    assert ok
    assert_close_to_reference(served, reference(cfg, key, toks, pos),
                              reference(cfg, key, toks, pos, quant="fp8"))


def _uncut(cfg):
    """The reduced model with every routed expert held: the uncut layer."""
    return cfg.with_(moe=dataclasses.replace(cfg.moe, experts_held=0))


@pytest.mark.parametrize("shares", [4])
def test_expert_shares_add_up_to_the_uncut_reference_layer(shares):
    """(b) The routed parts the shares of held experts give, plus the shared
    experts counted once, are the whole layer of the reference."""
    from bench.refs import mla_moe
    cfg = _uncut(get_reduced(ARCH)).with_(dtype="float32")
    m = cfg.moe
    p = L.init_moe_held(cfg, jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (2, 6, cfg.d_model))
    per = m.num_experts // shares
    with jax.default_matmul_precision("highest"):
        total = L.mlp_apply(cfg.with_(d_ff=m.d_shared, moe=None),
                            p["shared"], x)
        for i in range(shares):
            part = dict(p, experts=jax.tree.map(
                lambda a: a[i * per:(i + 1) * per], p["experts"]))
            del part["shared"]
            share = cfg.with_(moe=dataclasses.replace(m, experts_held=per))
            out, routes = L.moe_held(share, part, x, first=i * per)
            total = total + out
        c = dict(num_experts_per_tok=m.top_k, n_routed_experts=m.num_experts,
                 routed_scaling_factor=m.route_scale)
        w = {"router": p["router"], "bias": p["score_bias"],
             "wi": p["experts"]["wi"], "wg": p["experts"]["wg"],
             "wm": p["experts"]["wo"],
             "shared": {"wi": p["shared"]["wi"], "wg": p["shared"]["wg"],
                        "wm": p["shared"]["wo"]}}
        ref = mla_moe._moe(c, w, x, None)
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_correction_bias_picks_the_experts_and_scores_weight_them():
    """(c) Selection is the top-k of sigmoid score + bias; the weights are
    the unbiased scores of the picks, normalised and scaled by 2.446."""
    cfg = get_reduced(ARCH).with_(dtype="float32")
    m = cfg.moe
    p = L.init_moe_held(cfg, jax.random.key(7))
    x = jax.random.normal(jax.random.key(8), (64, cfg.d_model))
    w, idx = L.moe_route(cfg, p, x)
    scores = jax.nn.sigmoid(x @ p["router"])
    _, want = jax.lax.top_k(scores + p["score_bias"], m.top_k)
    _, unbiased = jax.lax.top_k(scores, m.top_k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))
    assert bool(jnp.any(jnp.sort(idx, -1) != jnp.sort(unbiased, -1)))
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(picked / picked.sum(-1, keepdims=True)
                                  * 2.446), rtol=1e-5)
    assert m.route_scale == 2.446 and get_config(ARCH).moe.route_scale == 2.446


def test_chunk_logits_do_not_depend_on_batch_mates():
    """(d) Dropless: a prompt's chunk-step logits are the same alone and
    beside prompts that send every token to the same experts (which a
    capacity of T k / E x 1.25 per expert would drop)."""
    cfg = get_reduced(ARCH)
    params = T.init_params(cfg, jax.random.key(9))
    b, c, mb, bs = 3, 8, 4, 4
    pools = MC.paged_pool_init(cfg, 1 + b * mb, bs)
    tables = jnp.asarray(1 + np.arange(b)[:, None] * mb + np.arange(mb),
                         jnp.int32)
    wc = jnp.zeros((1 + b * mb,), jnp.uint32)
    prompt = _toks(cfg, 1, c, 4)[0]
    step = jax.jit(lambda tok, cl: PG.chunk_logits(
        cfg, params, pools, tables, jnp.zeros((b,), jnp.int32), wc, tok, cl,
        None)[0])
    alone = step(jnp.zeros((b, c), jnp.int32).at[0].set(prompt),
                 jnp.asarray([c, 0, 0], jnp.int32))
    crowd = jnp.stack([prompt, jnp.full((c,), prompt[0]),
                       jnp.full((c,), prompt[0])])
    beside = step(crowd, jnp.asarray([c, c, c], jnp.int32))
    np.testing.assert_array_equal(np.asarray(alone[0]), np.asarray(beside[0]))


def test_sealed_gmm_decrypts_each_expert_under_its_own_pad():
    """(e) The kernel (interpret mode) is a plain grouped matmul of the
    plaintext experts; experts of equal plaintext get different
    ciphertext; a flipped bit in one expert's tile fails its MAC."""
    seal = SealConfig(mode="coloe", smart_ratio=0.5, verify=True)
    e, k, n = 4, 64, 32
    w = jax.random.normal(jax.random.key(1), (2, e, k, n))
    w = w.at[:, 1].set(w[:, 0])                       # twin experts
    tree = {"blocks": ({"mlp": {"experts": {"wi": w}}},)}
    sp = SS.seal_params(tree, seal, KEY)
    st = sp.tensors["blocks/0/mlp/experts/wi"]
    assert st.meta.layout == "tiles" and st.meta.n_batch == 2
    assert st.meta.fused and "blocks/0/mlp/experts/wi" in sp.fused_paths()
    ct = np.asarray(st.payload)
    assert not np.array_equal(ct[0, 0], ct[0, 1])    # twins, other pads
    assert not np.array_equal(ct[0, 0], ct[1, 0])    # layers, other pads
    np.testing.assert_array_equal(np.asarray(SS.unseal_params(sp, KEY)
                                             ["blocks"][0]["mlp"]["experts"]
                                             ["wi"]), np.asarray(w))
    xs = jax.random.normal(jax.random.key(2), (e, 8, k))
    layer = jax.tree.map(lambda a: a[1], st)          # as a layer scan slices
    got = layer.gmm(xs)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jnp.einsum("etk,ekn->etn", xs, w[1])),
        rtol=1e-5, atol=1e-5)
    oracle = KR.sealed_gmm_ref(xs, layer.payload, layer.key_words[0],
                               jnp.asarray(st.meta.nonce, jnp.uint32),
                               st.meta.bk, st.meta.bn, layer.row_mask,
                               layer.wc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                               rtol=1e-6, atol=1e-6)
    assert bool(SS.verify_params(sp, KEY))
    flipped = st.payload.at[1, 2, 5, 3].set(st.payload[1, 2, 5, 3] ^ 1)
    sp.tensors["blocks/0/mlp/experts/wi"] = SS.SealedTensor(
        flipped, st.counters, st.row_mask, st.key_words, st.wc, st.meta,
        macs=st.macs)
    assert not bool(SS.verify_params(sp, KEY))


def test_sealed_gmm_pads_slabs_to_whole_sublanes():
    e, t, k, n = 2, 3, 32, 16
    w = jax.random.normal(jax.random.key(5), (e, k, n))
    key = jnp.arange(8, dtype=jnp.uint32)
    nonce = jnp.asarray([1, 3, 5], jnp.uint32)
    mask = jnp.ones((e, k), bool)
    wc = jnp.asarray([4, 9], jnp.uint32)
    ct = jnp.stack([KR.seal_weights_ref(w[i], key, nonce, 32, 16, mask[i],
                                        wc[i]) for i in range(e)])
    xs = jax.random.normal(jax.random.key(6), (e, t, k))
    got = ops.sealed_gmm(xs, ct, mask, key, nonce, wc, bk=32, bn=16)
    assert got.shape == (e, t, n)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum("etk,ekn->etn", xs, w)),
                               rtol=1e-5, atol=1e-5)


def _engine(seed=0):
    cfg = get_reduced(ARCH)
    params = T.init_params(cfg, jax.random.key(seed))
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, seal=None,
                      seal_cache=True, verify=True)
    rng = np.random.RandomState(seed)
    for n in (9, 13):
        eng.submit(rng.randint(0, cfg.vocab_size, n), max_tokens=6)
    return cfg, eng


def test_route_counters_accumulate_on_device_without_tick_readback():
    """Counters of (token, expert) pairs: every real token's pairs, and
    those on held experts, summed on the device; a decode tick moves
    nothing host to device, and the engine reads them only from ``stats``."""
    cfg, eng = _engine(1)
    while any(p is not None for p in eng._pending) or eng.queue:
        eng.step()
    eng._decode_tick()
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(2):
            eng._decode_tick()
    eng.run()
    eng.check_device_mirror()
    moe_layers = cfg.num_layers - cfg.first_dense
    fed = 9 + 13 + 2 * 5            # prompts, then every decoded input
    st = eng.stats
    assert st["moe_routes"] == fed * cfg.moe.top_k * moe_layers
    assert 0 < st["moe_routes_held"] < st["moe_routes"]


def test_latent_pool_holds_one_entry_per_token():
    cfg = get_config("moonlight_16b_a3b_ep8")
    spec = MC.paged_pool_spec(cfg, 2049, 16)[0]
    assert set(spec) == {"c", "mac_c", "lid"}
    assert MC.kv_words_per_token(cfg) == 288
    assert spec["c"].shape == (16, 2049, 16 * 288)


def _program(text: str) -> str:
    """Optimised HLO text without its source metadata, every value renamed
    by its first appearance (as ``test_serve_tracing`` compares them)."""
    text = re.sub(r",?\s*metadata=\{[^}]*\}", "", text)
    text = text[text.index("\n%"):]
    text = re.sub(r"([(,] ?)([\w.\-]+): ", r"\1%\2: ", text)
    names = {}
    return re.sub(r"%([\w.\-]+)",
                  lambda m: f"%v{names.setdefault(m.group(1), len(names))}",
                  text)


def test_moe_and_mla_scopes_name_operations_and_change_none(monkeypatch):
    _, eng = _engine(2)
    eng.run()
    args = eng._decode_args()

    def compiled():
        return jax.jit(lambda *a: eng._decode_fn(*a)).lower(*args).compile()

    scoped = compiled().as_text()
    ops_ = re.findall(r'op_name="([^"]*)"', scoped)
    for scope in ("mla_absorb", "moe_route", "moe_experts", "moe_shared",
                  "kv_view", "kv_gather", "kv_mac", "kv_unseal", "kv_mask",
                  "kv_append", "attention"):
        assert any(f"/{scope}/" in o for o in ops_), scope
    assert any("/kv_view/kv_mac/" in o for o in ops_)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled().as_text()
    assert "moe_experts" not in plain and "mla_absorb" not in plain
    assert _program(scoped) == _program(plain)
