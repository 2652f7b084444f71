"""Paged, tile-sealed KV cache + continuous-batching scheduler.

Layer 1 (pure functions, f32): teacher-forced decode over the paged pools
reproduces the contiguous cache's logits, on a dense and a GQA head layout,
with the pools plaintext or sealed. The paged attention kernel sums the same
terms as the contiguous cache's attention in another order (an online
softmax over groups of blocks, the fresh token merged last), so the two
agree to f32 rounding (1e-5), not bit for bit; sealed and plaintext pools
feed the kernel the same plaintext, so they agree exactly with each other.

Layer 2 (engine, bf16): the continuous scheduler under staggered arrivals
completes everything, returns every block to the allocator, and a sealed
cache produces the exact token streams of a plaintext cache across mixed
sampling settings.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import sealed_store as SS
from repro.models import cache as MC
from repro.models import paged as PG
from repro.models import transformer as T
from repro.serve.engine import ServeEngine

BS = 4          # block size (tokens) for the pure-function tests
PLEN, STEPS = 8, 6


def _paged_teacher_forced(cfg, params, toks, seal):
    """Prefill + teacher-forced decode through the paged pools; returns
    per-step logits stacked (1 + STEPS, B, V)."""
    b = toks.shape[0]
    mb = (PLEN + STEPS + BS - 1) // BS + 1
    nb = 1 + b * mb
    pools = MC.paged_pool_init(cfg, nb, BS)
    tables = np.zeros((b, mb), np.int32)
    for i in range(b):
        tables[i] = 1 + i * mb + np.arange(mb)
    wc = np.zeros((nb,), np.uint32)
    nblk = PLEN // BS
    block_tables = tables[:, :nblk]

    logits, cache = PG.prefill_logits(cfg, params, toks[:, :PLEN],
                                      jnp.full((b,), PLEN, jnp.int32))
    wc[block_tables] += 1                    # sealed under the bumped wc
    pools = PG.prefill_write(cfg, seal, pools, cache,
                             jnp.asarray(block_tables), jnp.asarray(wc))
    out = [logits]
    tables = jnp.asarray(tables)
    wc = jnp.asarray(wc)
    lengths = jnp.full((b,), PLEN, jnp.int32)
    ones = jnp.ones((b,), jnp.int32)

    @jax.jit
    def step(pools, lengths, wc, step_tok):
        logits, updates, _ = PG.decode_logits(
            cfg, params, pools, tables, lengths, wc, step_tok, seal)
        pools, wc = PG.append_tokens(cfg, seal, pools, updates, tables,
                                     lengths, ones, wc)
        return logits, pools, wc

    for t in range(STEPS):
        logits, pools, wc = step(pools, lengths, wc,
                                 toks[:, PLEN + t][:, None])
        lengths = lengths + 1
        out.append(logits)
    return jnp.stack(out)


@pytest.mark.parametrize("kv_heads", [4, 2])     # dense MHA / GQA
@pytest.mark.parametrize("sealed", [False, True])
def test_paged_matches_contiguous_logits_exactly(kv_heads, sealed):
    cfg = get_reduced("internlm2_1_8b").with_(dtype="float32",
                                              num_kv_heads=kv_heads)
    params = T.init_params(cfg, jax.random.key(0))
    rng = np.random.RandomState(3)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, PLEN + STEPS)),
                       jnp.int32)
    seal = SS.cache_seal_config(bytes(range(32))) if sealed else None
    paged = _paged_teacher_forced(cfg, params, toks, seal)

    mb = (PLEN + STEPS + BS - 1) // BS + 1
    logits, cache = T.prefill(cfg, params, {"tokens": toks[:, :PLEN]},
                              mb * BS)
    ref = [logits]
    for t in range(STEPS):
        logits, cache, _ = T.decode_step(cfg, params, cache,
                                         {"tokens": toks[:, PLEN + t][:, None]},
                                         jnp.int32(PLEN + t))
        ref.append(logits)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(jnp.stack(ref)),
                               rtol=1e-5, atol=1e-5)


def _run_engine(cfg, params, seal_cache, reqs):
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, seal=None,
                      seal_cache=seal_cache, sample_seed=5)
    for prompt, kw in reqs:
        eng.submit(prompt, **kw)
    done = eng.run()
    assert all(r.done for r in done) and len(done) == len(reqs)
    return eng, {r.rid: r.out for r in done}


def test_sealed_cache_tokens_bit_identical_to_plaintext():
    """Acceptance: sealed-cache serving emits the exact token stream of the
    plaintext-cache path, across mixed lengths and sampling settings."""
    cfg = get_reduced("internlm2_1_8b")
    params = T.init_params(cfg, jax.random.key(1))
    rng = np.random.RandomState(0)
    reqs = [
        (rng.randint(0, cfg.vocab_size, 5), dict(max_tokens=6)),
        (rng.randint(0, cfg.vocab_size, 12),
         dict(max_tokens=8, temperature=0.8, top_k=5)),
        (rng.randint(0, cfg.vocab_size, 19),
         dict(max_tokens=5, temperature=1.0, top_p=0.9)),
        (rng.randint(0, cfg.vocab_size, 8),
         dict(max_tokens=7, temperature=0.6)),
    ]
    eng_p, out_plain = _run_engine(cfg, params, False, reqs)
    eng_s, out_seal = _run_engine(cfg, params, True, reqs)
    assert out_plain == out_seal
    # the metric follows: a sealed cache contributes zero plaintext traffic
    assert eng_p.stats["kv_plaintext_bytes_per_step"] > 0
    assert eng_s.stats["kv_plaintext_bytes_per_step"] == 0


def test_continuous_scheduler_staggered_arrivals():
    """Slots are reused across staggered arrivals, everything completes,
    and the allocator gets every block back."""
    cfg = get_reduced("internlm2_1_8b")
    params = T.init_params(cfg, jax.random.key(2))
    rng = np.random.RandomState(1)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, seal=None,
                      seal_cache=False)
    handles = []
    prompts = [rng.randint(0, cfg.vocab_size, rng.randint(4, 20))
               for _ in range(5)]
    for i, p in enumerate(prompts):
        handles.append(eng.submit(p, max_tokens=4 + i))
        eng.step()                      # arrivals interleave with decoding
    while eng.busy:
        eng.step()
    assert all(r.done for r in handles)
    assert eng.stats["prefills"] >= 3       # slots refilled mid-stream
    assert len(eng._free) == eng.num_blocks - 1
    assert all(r is None for r in eng._active)
    assert not np.any(eng._tables) and not np.any(eng._lengths)

    # greedy decoding is slot-placement independent: a solo engine gives
    # request 0 the identical continuation
    solo = ServeEngine(cfg, params, batch_slots=2, max_len=48, seal=None,
                       seal_cache=False)
    r = solo.submit(prompts[0], max_tokens=4)
    solo.run()
    assert r.out == handles[0].out


# ---------------------------------------------------------------------------
# chunked prefill, prefix sharing, device-resident scheduler (PR 3)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sealed", [False, True])
def test_chunked_prefill_matches_one_shot_exactly(sealed):
    """A prompt prefilled in ragged fixed-width chunks produces the one-shot
    ``prefill_logits`` output: each chunk token attends the pool prefix
    through the paged attention kernel and the chunk's own keys causally,
    the same terms as a contiguous prefill summed in another order, so the
    two agree to f32 rounding (1e-5)."""
    cfg = get_reduced("internlm2_1_8b").with_(dtype="float32")
    params = T.init_params(cfg, jax.random.key(0))
    rng = np.random.RandomState(3)
    plen, b, mb = 11, 2, 5
    nb = 1 + b * mb
    toks = rng.randint(0, cfg.vocab_size, (b, plen)).astype(np.int32)
    seal = SS.cache_seal_config(bytes(range(32))) if sealed else None

    pools = MC.paged_pool_init(cfg, nb, BS)
    tables = np.zeros((b, mb), np.int32)
    for i in range(b):
        tables[i] = 1 + i * mb + np.arange(mb)
    wc = jnp.zeros((nb,), jnp.uint32)
    lengths = jnp.zeros((b,), jnp.int32)
    chunk_w, off, last = 5, 0, None

    @jax.jit
    def step(pools, lengths, wc, chunk, cl):
        last, ups, _ = PG.chunk_logits(cfg, params, pools,
                                       jnp.asarray(tables), lengths, wc,
                                       chunk, cl, seal)
        pools, wc = PG.append_tokens(cfg, seal, pools, ups,
                                     jnp.asarray(tables), lengths, cl, wc)
        return last, pools, wc

    while off < plen:
        n = min(chunk_w, plen - off)
        chunk = np.zeros((b, chunk_w), np.int32)
        chunk[:, :n] = toks[:, off:off + n]
        cl = jnp.full((b,), n, jnp.int32)
        last, pools, wc = step(pools, lengths, wc, jnp.asarray(chunk), cl)
        lengths = lengths + cl
        off += n

    pad = np.zeros((b, mb * BS), np.int32)
    pad[:, :plen] = toks
    ref, _ = PG.prefill_logits(cfg, params, jnp.asarray(pad),
                               jnp.full((b,), plen, jnp.int32))
    np.testing.assert_allclose(np.asarray(last), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("seal_cache", [False, True])
def test_prefix_sharing_bit_identical_to_unshared(seal_cache):
    """Requests sharing a prompt prefix (full blocks and a copy-on-write
    partial tail block) emit the exact token streams of an unshared run,
    on plaintext and sealed pools."""
    cfg = get_reduced("internlm2_1_8b")
    params = T.init_params(cfg, jax.random.key(1))
    rng = np.random.RandomState(7)
    base = rng.randint(0, cfg.vocab_size, 27)    # 1 full block + 11 tail
    fork = np.concatenate([base[:20], rng.randint(0, cfg.vocab_size, 7)])

    def run(prefix_share):
        eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, seal=None,
                          seal_cache=seal_cache, sample_seed=5,
                          prefix_share=prefix_share)
        r0 = eng.submit(base.copy(), max_tokens=6)
        for _ in range(3):
            eng.step()        # donor registers its prefix before the others
        r1 = eng.submit(base.copy(), max_tokens=6,
                        temperature=0.7, top_k=8)
        r2 = eng.submit(fork.copy(), max_tokens=5)
        eng.run()
        eng.check_device_mirror()
        return eng, (r0.out, r1.out, r2.out)

    eng_u, out_u = run(False)
    eng_s, out_s = run(True)
    assert out_u == out_s
    assert eng_s.stats["cow_copies"] >= 1            # partial tail was COWed
    assert eng_s.stats["shared_prefix_blocks"] >= 2
    assert eng_s.stats["shared_prefix_tokens"] >= 26  # plen-1 for the clone
    assert eng_u.stats["shared_prefix_blocks"] == 0


def test_refcounted_blocks_freed_with_last_reader():
    """Shared blocks return to the free list only when the last reader —
    live slot or registry entry — drops them; registry-held blocks are
    reclaimed by LRU eviction under pressure, not on request finish."""
    cfg = get_reduced("internlm2_1_8b")
    params = T.init_params(cfg, jax.random.key(1))
    rng = np.random.RandomState(9)
    base = rng.randint(0, cfg.vocab_size, 27)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, seal=None,
                      seal_cache=False, prefix_share=True)
    eng.submit(base.copy(), max_tokens=4)
    eng.run()
    # donor finished: its prompt blocks stay pinned by the registry
    held = eng.num_blocks - 1 - len(eng._free)
    assert held == 2                    # 1 full prefix block + partial tail
    shared_block = eng._registry._full[next(iter(eng._registry._full))]
    assert eng._alloc.refcount[shared_block] == 1   # registry is sole reader

    eng.submit(base.copy(), max_tokens=4)
    eng._admit()
    assert eng._alloc.refcount[shared_block] == 2   # + the live slot
    eng.run()
    assert eng._alloc.refcount[shared_block] == 1   # back to registry-only
    assert eng.num_blocks - 1 - len(eng._free) >= 2
    # under pressure the registry lets LRU chains go
    eng._registry.evict_lru(eng.num_blocks - 1)
    assert len(eng._free) == eng.num_blocks - 1
    eng.check_device_mirror()


def test_decode_tick_is_host_free():
    """Acceptance: with the scheduler state device-resident, a steady-state
    decode tick performs NO host->device transfer — the sampled token vector
    is the only traffic, and it goes the other way."""
    cfg = get_reduced("internlm2_1_8b")
    params = T.init_params(cfg, jax.random.key(2))
    rng = np.random.RandomState(4)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, seal=None,
                      seal_cache=True)
    eng.submit(rng.randint(0, cfg.vocab_size, 9), max_tokens=24)
    eng.submit(rng.randint(0, cfg.vocab_size, 13), max_tokens=24)
    while any(p is not None for p in eng._pending) or eng.queue:
        eng.step()                      # admission + chunked prefill
    eng._decode_tick()                  # warm the decode graph
    steps = eng.stats["decode_steps"]
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(3):
            eng._decode_tick()
    assert eng.stats["decode_steps"] == steps + 3
    eng.run()
    eng.check_device_mirror()
