"""Substrate tests: optimizer, schedule, grad compression, data pipeline,
checkpointing (sealed/atomic/async), elastic rescale, fault machinery,
serve engine, distributed small-mesh integration."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.manager import CheckpointManager, rebuild_tree
from repro.config import SealConfig, TrainConfig
from repro.configs import get_reduced
from repro.data.loader import PrefetchLoader
from repro.data.synthetic import TokenStream, image_dataset, lm_batch
from repro.models import transformer as T
from repro.optim import adamw, grad_compress, schedule
from repro.runtime.fault import (Heartbeat, PreemptionGuard, StepWatchdog,
                                 StragglerTimeout, retry)
from repro.serve.engine import ServeEngine


# ---------------- optimizer ----------------

def test_adamw_reduces_loss_quadratic():
    tc = TrainConfig(learning_rate=0.1, weight_decay=0.0, warmup_steps=0,
                     total_steps=100)
    params = {"w": jnp.array([3.0, -2.0])}
    opt = adamw.init(params)
    for i in range(60):
        grads = {"w": 2 * params["w"]}
        params, opt, _ = adamw.update(params, opt, grads, 0.1, tc)
    assert float(jnp.sum(params["w"] ** 2)) < 0.05


def test_grad_clip():
    g = {"a": jnp.full((4,), 100.0)}
    clipped, norm = adamw.clip_by_global_norm(g, 1.0)
    assert float(adamw.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    assert float(norm) == pytest.approx(200.0, rel=1e-5)


def test_schedule_warmup_cosine():
    tc = TrainConfig(learning_rate=1.0, warmup_steps=10, total_steps=100)
    lrs = [float(schedule.lr_at(jnp.int32(s), tc)) for s in [0, 9, 10, 50, 99]]
    assert lrs[0] < lrs[1] <= lrs[2]
    assert lrs[2] >= lrs[3] >= lrs[4]
    assert lrs[4] >= 0.09   # floor


# ---------------- gradient compression ----------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**30), scale=st.floats(1e-4, 1e3))
def test_compress_roundtrip_bounded_error(seed, scale):
    g = jax.random.normal(jax.random.key(seed), (128,)) * scale
    codes, s = grad_compress.compress(g)
    back = grad_compress.decompress(codes, s)
    assert float(jnp.max(jnp.abs(back - g))) <= float(s) / 2 + 1e-9


def test_error_feedback_accumulates():
    g = jnp.array([1.0, 1e-4, -1e-4])   # tiny components lost per step
    err = jnp.zeros_like(g)
    total_sent = jnp.zeros_like(g)
    for _ in range(200):
        ghat, err = grad_compress.ef_step(g, err)
        total_sent += ghat
    # with EF, the mean transmitted gradient converges to the true one
    # (within one int8 quantum over the horizon)
    np.testing.assert_allclose(np.asarray(total_sent / 200), np.asarray(g),
                               rtol=0.25, atol=5e-5)
    # without EF the tiny components would never be transmitted at all
    codes, s = grad_compress.compress(g)
    assert int(codes[1]) == 0 and float(total_sent[1]) > 0


def test_allreduce_compressed_shard_map():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:1]), ("pod",))

    def f(g):
        return grad_compress.allreduce_compressed(g, "pod")

    g = jnp.arange(8.0)
    out = shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), rtol=0.02,
                               atol=1e-4)


# ---------------- data ----------------

def test_tokenstream_deterministic_and_sharded():
    ts = TokenStream(1000, 32, 8, seed=3)
    a = ts.batch_at(5)
    b = ts.batch_at(5)
    assert np.array_equal(a["tokens"], b["tokens"])
    # shards partition the batch deterministically
    sh0 = TokenStream(1000, 32, 8, seed=3, n_shards=2, shard=0).batch_at(5)
    sh1 = TokenStream(1000, 32, 8, seed=3, n_shards=2, shard=1).batch_at(5)
    assert sh0["tokens"].shape == (4, 32)
    assert not np.array_equal(sh0["tokens"], sh1["tokens"])
    # targets are next-token shifted
    assert np.array_equal(a["tokens"][:, 1:], a["targets"][:, :-1])


def test_prefetch_loader():
    seen = []
    loader = PrefetchLoader(lambda s: {"x": np.full((2,), s)}, start_step=3)
    for step, batch in loader:
        seen.append((step, int(batch["x"][0])))
        if len(seen) >= 4:
            break
    loader.close()
    assert seen == [(3, 3), (4, 4), (5, 5), (6, 6)]


def test_image_dataset_learnable_classes():
    x, y = image_dataset(64, img=16, seed=0)
    assert x.shape == (64, 16, 16, 3) and set(np.unique(y)) <= set(range(10))


# ---------------- checkpointing ----------------

def test_checkpoint_roundtrip_sealed(tmp_path):
    cfg = get_reduced("internlm2_1_8b")
    params = T.init_params(cfg, jax.random.key(0))
    opt = adamw.init(params)
    mgr = CheckpointManager(str(tmp_path), seal=SealConfig(mode="coloe"))
    mgr.save(7, params, opt, blocking=True)
    step, host = mgr.restore()
    assert step == 7
    back = rebuild_tree(jax.eval_shape(lambda: params), host["params"])
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert bool(jnp.all(a == b))
    # sealed at rest: stored bytes are NOT the raw weights
    import glob
    raw = np.load(glob.glob(str(tmp_path / "step_00000007" / "params__embed.w.npy"))[0])
    assert raw.dtype == np.uint32    # ciphertext lines, not f32 weights


def test_checkpoint_atomicity_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    p = {"w": jnp.arange(4.0)}
    for s in [1, 2, 3]:
        mgr.save(s, p, blocking=True)
    assert mgr.list_steps() == [2, 3]
    # a .tmp dir is never listed as a checkpoint
    os.makedirs(tmp_path / "step_00000009.tmp")
    assert 9 not in mgr.list_steps()


def test_checkpoint_detects_corruption(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.arange(8.0)}, blocking=True)
    f = list((tmp_path / "step_00000001").glob("*.npy"))[0]
    data = f.read_bytes()
    f.write_bytes(data[:-4] + b"\x00\x00\x00\x01")
    with pytest.raises(IOError):
        mgr.restore()


def test_elastic_rescale(tmp_path):
    """Save under one sharding, restore onto a different mesh."""
    from repro.runtime.elastic import candidate_meshes, rescale
    cfg = get_reduced("granite_3_2b")
    params = T.init_params(cfg, jax.random.key(0))
    opt = adamw.init(params)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(11, params, opt, blocking=True)
    assert candidate_meshes(8) == [(1, 8), (2, 4), (4, 2), (8, 1)]
    step, p2, o2, mesh = rescale(cfg, mgr, devices=jax.devices())
    assert step == 11
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        assert bool(jnp.all(a == jnp.asarray(b)))


# ---------------- fault tolerance ----------------

@pytest.mark.parametrize("env", ["set", "unset"])
def test_compile_cache_dir(env, monkeypatch):
    """The entry points' cache is $JAX_COMPILATION_CACHE_DIR when set, and
    otherwise the fixed ``.jax_cache`` at the root of the checkout."""
    from repro.runtime import compile_cache
    was = jax.config.jax_compilation_cache_dir
    if env == "set":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        return
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable()
        assert got == jax.config.jax_compilation_cache_dir
        assert got == os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_heartbeat_detects_dead_host(tmp_path):
    hb1 = Heartbeat(str(tmp_path), "h1", timeout=0.5)
    hb2 = Heartbeat(str(tmp_path), "h2", timeout=0.5)
    hb1.beat(step=5)
    hb2.beat(step=5)
    assert set(hb1.alive_hosts()) == {"h1", "h2"}
    time.sleep(0.7)
    hb1.beat(step=6)
    assert set(hb1.alive_hosts()) == {"h1"}
    assert set(hb1.dead_hosts()) == {"h2"}


def test_step_watchdog_flags_straggler():
    wd = StepWatchdog(margin=2.0, warmup_steps=3)
    for _ in range(10):
        wd.check(0.1)
    with pytest.raises(StragglerTimeout):
        wd.check(1.0)


def test_retry_backoff():
    calls = []

    @retry(n=3, backoff=0.01)
    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise IOError("transient")
        return 42

    assert flaky() == 42 and len(calls) == 3


def test_preemption_guard_flag():
    g = PreemptionGuard(install=False)
    assert not g.requested
    g.trigger()
    assert g.requested


# ---------------- serving ----------------

@pytest.mark.parametrize("seal_mode", ["none", "coloe"])
def test_serve_engine_batched(seal_mode):
    cfg = get_reduced("internlm2_1_8b")
    params = T.init_params(cfg, jax.random.key(0))
    seal = None if seal_mode == "none" else SealConfig(mode=seal_mode,
                                                       smart_ratio=0.5)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, seal=seal)
    rng = np.random.RandomState(0)
    reqs = [eng.submit(rng.randint(0, cfg.vocab_size, size=8), max_tokens=6)
            for _ in range(3)]
    done = eng.run()
    assert len(done) == 3
    assert all(len(r.out) >= 1 for r in done)
    assert eng.stats["decode_steps"] > 0


def test_sealed_serving_matches_plaintext_serving():
    cfg = get_reduced("granite_3_2b").with_(dtype="float32")
    params = T.init_params(cfg, jax.random.key(0))
    prompt = np.arange(8) % cfg.vocab_size
    outs = []
    for seal in [None, SealConfig(mode="coloe", smart_ratio=0.5)]:
        eng = ServeEngine(cfg, params, batch_slots=1, max_len=32, seal=seal)
        r = eng.submit(prompt, max_tokens=5)
        eng.run()
        outs.append(tuple(r.out))
    assert outs[0] == outs[1]   # decryption is exact: same tokens


# ---------------- small-mesh distributed integration ----------------

def test_train_loop_runs_and_resumes(tmp_path):
    from repro.launch.mesh import make_host_mesh
    from repro.train.loop import train
    cfg = get_reduced("internlm2_1_8b")
    tc = TrainConfig(learning_rate=1e-3, total_steps=6, warmup_steps=1,
                     microbatches=2, checkpoint_every=3,
                     checkpoint_dir=str(tmp_path), async_checkpoint=False)
    mesh = make_host_mesh(data=1, model=1)
    p, o, m = train(cfg, tc, mesh, batch=4, seq=16, steps=4, log_path=None)
    assert np.isfinite(m["loss"])
    mgr = CheckpointManager(str(tmp_path))
    assert 3 in mgr.list_steps()
    # resume continues from step 3
    p, o, m2 = train(cfg, tc, mesh, batch=4, seq=16, steps=6, log_path=None)
    assert int(o["step"]) >= 3
