"""Smoke test of the sealed serving path on one TPU chip.

    python chip_smoke.py               # phases (a)-(d) on one chip
    python chip_smoke.py --four-chip   # sharded training only, on four chips
    python chip_smoke.py --reduced     # tiny rehearsal of (b)-(d); exits 1

One process runs every phase, in order, at the published widths of
internlm2_1_8b (24 layers, d_model 2048, GQA 16/8, d_ff 8192, vocab 92544)
with random weights made from a seed:

  (a) the Pallas kernels on the chip (``interpret=False``) against their
      references in ``repro.kernels.ref``;
  (b) plaintext serving through ``repro.launch.serve`` (8 slots, 8 greedy
      requests, prompts of 64-128 tokens, 16 new tokens each);
  (c) the same requests over ColoE-sealed weights and a sealed KV cache:
      every request completes, matmul leaves take the fused kernel, and the
      token streams equal those of (b), or depart from them only at
      near-ties of the plaintext logits (``serve_phase``);
  (d) (c) with the integrity MACs armed and one bitflip injected into the
      sealed cache: the fault is detected, the victim re-prefilled, and
      every other request streams exactly the tokens of (c).

``--four-chip`` runs only the path that spans chips: three training steps
(depth cut to 4 layers, batch 8 x 512, 2 microbatches) on a (data 2,
model 2) mesh, against the same steps on one chip, in the same process.

Each phase prints compile seconds, run seconds, tokens and device memory
(``phase``). No phase's failure is caught: any failure exits nonzero.
The last line, printed only on a TPU and never with ``--reduced``, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime import compile_cache  # noqa: E402

SEED = 0
KEYW = np.frombuffer(bytes(range(32)), np.uint32)
NONCE = np.array([7, 11, 13], np.uint32)
# one kernel call, bf16 operands and f32 accumulation: error bound relative
# to the output scale
BF16_TOL = 2e-2
# the model's logits after 24 bf16 layers, sealed against plaintext: four
# bf16 ulps (2^-7 each) of the largest logit
LOGIT_TOL = 2.0 ** -5
# phase (a): the (K, N) of every fused matmul leaf of internlm2_1_8b (attention
# and MLP in, MLP out, LM head), decode and prefill row counts, and the
# attention length
KERNEL_KN = [(2048, 8192), (8192, 2048), (2048, 92544)]
KERNEL_M = (8, 256)
FLASH_T = 2048


class CompileClock:
    """Sums the seconds JAX spends compiling (or fetching a compiled program
    from the persistent cache), read off JAX's own monitoring events."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def device_facts():
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_stats():
    return jax.devices()[0].memory_stats() or {}


class MemorySampler:
    """Highest ``bytes_in_use`` seen while a phase runs, polled from a
    thread. The allocator's own ``peak_bytes_in_use`` counts from the start
    of the process, so a phase that peaks lower than an earlier one would
    not show in it; the sampled peak is per phase, and may miss a spike
    shorter than the poll interval."""

    def __init__(self, interval_s=0.002):
        self.interval_s = interval_s
        self.peak = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)

    def _poll(self):
        while not self._stop.is_set():
            used = memory_stats().get("bytes_in_use")
            if used is not None:
                self.peak = max(self.peak or 0, used)
            time.sleep(self.interval_s)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()


def phase(name, clock, fn):
    """Run one phase; print its bring-up facts. Failures propagate. The
    phase's arrays and compiled programs are dropped before the next one."""
    c0, t0 = clock.seconds, time.perf_counter()
    with MemorySampler() as mem:
        out = fn() or {}
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    jax.clear_caches()
    gc.collect()
    stats = memory_stats()
    rec = {"phase": name, "compile_s": round(comp, 3),
           "run_s": round(wall - comp, 3), **out,
           "sampled_peak_bytes": mem.peak,
           "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
           "bytes_in_use_after": stats.get("bytes_in_use")}
    print(json.dumps(rec), flush=True)
    return out


def check(ok, what):
    """A smoke check: raises (also under ``python -O``) when ``ok`` is
    false."""
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(np.all(np.isfinite(got)), "non-finite kernel output")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def kernels_phase():
    """(a) The three Pallas kernels, compiled by Mosaic, against refs."""
    from repro.kernels import chacha20 as CC
    from repro.kernels import flash_attention as FA
    from repro.kernels import ref
    from repro.kernels import sealed_matmul as SM
    from repro.models import layers as L

    key = jax.random.key(SEED)
    keyw, nonce = jnp.asarray(KEYW), jnp.asarray(NONCE)
    checked = {}
    for k, n in KERNEL_KN:
        kw, kx, km = jax.random.split(jax.random.fold_in(key, k * n), 3)
        w = jax.random.normal(kw, (k, n), jnp.float32) * 0.02
        mask = jax.random.bernoulli(km, 0.5, (k,))
        wc = 3
        wct = ref.seal_weights_ref(w, keyw, nonce, 128, 128, mask, wc)
        back = ref.unseal_weights_ref(wct, keyw, nonce, 128, 128, mask, wc)
        check(bool(jnp.all(jax.lax.bitcast_convert_type(back, jnp.uint32)
                           == jax.lax.bitcast_convert_type(w, jnp.uint32))),
              f"tile seal/unseal roundtrip at {k}x{n}")
        for m in KERNEL_M:
            x = jax.random.normal(jax.random.fold_in(kx, m), (m, k),
                                  jnp.float32)
            got = SM.sealed_matmul(
                x, wct, mask, keyw, nonce,
                jnp.asarray([wc], jnp.uint32), bm=min(m, 128), bk=128,
                bn=128, interpret=False, compute_dtype="bfloat16")
            with jax.default_matmul_precision("highest"):
                want = ref.sealed_matmul_ref(x, wct, keyw, nonce, 128, 128,
                                             mask, wc)
            err = _rel_err(got, want)
            check(err <= BF16_TOL, f"sealed_matmul {m}x{k}x{n}: {err}")
            checked[f"sealed_matmul_{m}x{k}x{n}_rel_err"] = err
        del w, wct, back

    ctr = jnp.arange(1 << 20, (1 << 20) + 65536, dtype=jnp.uint32)
    ks = CC.chacha20_keystream(keyw, nonce, ctr, tile=1024, interpret=False)
    check(bool(jnp.all(ks == ref.chacha20_keystream_ref(keyw, nonce, ctr))),
          "chacha20 keystream differs from the reference")
    checked["chacha20_keystream_bit_exact"] = True

    kq, kk, kv = jax.random.split(jax.random.fold_in(key, FLASH_T), 3)
    q = jax.random.normal(kq, (1, FLASH_T, 16, 128), jnp.bfloat16)
    kt = jax.random.normal(kk, (1, FLASH_T, 8, 128), jnp.bfloat16)
    vt = jax.random.normal(kv, (1, FLASH_T, 8, 128), jnp.bfloat16)
    scale = 128 ** -0.5
    got = FA.flash_attention(q, kt, vt, scale=scale, interpret=False)
    pos = jnp.arange(FLASH_T, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = L._sdpa(q.astype(jnp.float32), kt.astype(jnp.float32),
                       vt.astype(jnp.float32), L._attn_mask(pos, pos, 0),
                       0.0, scale)
    err = _rel_err(got, want)
    check(err <= BF16_TOL, f"flash_attention: {err}")
    checked["flash_attention_rel_err"] = err
    return checked


def serve_args(reduced, *extra):
    base = ["--arch", "internlm2_1_8b", "--slots", "8", "--requests", "8",
            "--prompt-len", "128", "--max-tokens", "16", "--seed",
            str(SEED), "--check"]
    if reduced:
        base[base.index("--prompt-len") + 1] = "32"
        base[base.index("--max-tokens") + 1] = "6"
    else:
        base.append("--production")
    return base + list(extra)


def teacher_logits(cfg, prompts, streams, seal_mode):
    """Logits at every generated position with the plaintext token streams
    fed back in (teacher forcing), over plaintext or sealed weights made
    from the launcher's seed. Returns (requests, new tokens, vocab) f32."""
    from repro.config import SealConfig
    from repro.core import sealed_store as SS
    from repro.models import transformer as T

    n_new = len(streams[0])
    seqs = [np.concatenate([p, s[:-1]]) for p, s in zip(prompts, streams)]
    width = -(-max(map(len, seqs)) // 16) * 16
    tokens = np.zeros((len(seqs), width), np.int32)
    for i, q in enumerate(seqs):
        tokens[i, :len(q)] = q
    idx = np.stack([len(p) - 1 + np.arange(n_new) for p in prompts])
    params = T.init_params(cfg, jax.random.key(0))
    key = bytes(range(32))
    if seal_mode == "none":
        tensors, view = params, (lambda t: t)
    else:
        sp = SS.seal_params(params, SealConfig(mode=seal_mode), key,
                            consume=True)
        tensors = sp.tensors

        def view(t):
            return SS.fused_params(
                SS.SealedParams(t, sp.plans, sp.treedef, sp.seal), key)
    del params

    @jax.jit
    def logits(t, tok, pos):
        p = view(t)
        h, _ = T.prefill_hidden(cfg, p, {"tokens": tok}, tok.shape[1])
        h = jnp.take_along_axis(h, pos[..., None], axis=1)
        return T._unembed(cfg, p, h)

    return np.asarray(logits(tensors, jnp.asarray(tokens), jnp.asarray(idx)))


def near_tie_check(cfg, prompts, want, got, rids, seal_mode):
    """Where a sealed stream departs from the plaintext one, the departure
    must be a near-tie that the chip's accumulation order can flip: the
    sealed and plaintext logits (teacher-forced on the plaintext streams)
    agree to ``LOGIT_TOL`` of the logit scale, and at the first differing
    position the plaintext logit gap between the two choices is within
    twice their observed difference there."""
    ref = teacher_logits(cfg, prompts, want, "none")
    gc.collect()
    sealed = teacher_logits(cfg, prompts, want, seal_mode)
    diff = np.abs(sealed - ref)
    rel = float(diff.max() / np.abs(ref).max())
    check(rel <= LOGIT_TOL, f"sealed logits differ by {rel} of their scale")
    ties = []
    for r in rids:
        t = next(i for i, (a, b) in enumerate(zip(want[r], got[r])) if a != b)
        gap = float(ref[r, t, want[r][t]] - ref[r, t, got[r][t]])
        bound = 2 * float(diff[r, t].max())
        check(gap <= bound,
              f"request {r} departs at token {t} where the plaintext logit "
              f"gap {gap} exceeds twice the sealed/plaintext difference "
              f"{bound}")
        ties.append({"request": r, "token": t, "gap": gap, "bound": bound})
    return {"logits_rel_err": rel, "near_ties": ties}


def launch(argv):
    """One launcher run, every request completed cleanly. Returns the
    parsed arguments, the run, its token streams and the phase record."""
    from repro.launch import serve
    args = serve.parse_args(argv)
    res = serve.run(args)
    check(res.ok, "the launcher's checks failed")
    check(all(r.done and r.error is None for r in res.requests),
          "a request did not complete cleanly")
    tokens = [list(r.out) for r in res.requests]
    out = {"requests": len(tokens), "tokens": sum(map(len, tokens)),
           "tokens_per_request": [len(t) for t in tokens]}
    if args.seal != "none":
        check(res.stats["fused_matmul_leaves"] > 0, "no fused matmul leaves")
        out["fused_matmul_leaves"] = res.stats["fused_matmul_leaves"]
    return args, res, tokens, out


def sealed_phase(argv, plain):
    """(c) Against ``plain`` (the plaintext run's tokens) every sealed
    stream must be equal or, where the chip's accumulation order breaks
    exact equality, depart only at near-ties (``near_tie_check``), which
    the phase then reports."""
    from repro.configs import get_config, get_reduced
    args, res, tokens, out = launch(argv)
    differ = [i for i, (a, b) in enumerate(zip(tokens, plain)) if a != b]
    out["equal_to_plaintext"] = len(tokens) - len(differ)
    if differ:
        print(f"sealed token streams differ from plaintext for requests "
              f"{differ}: checking the logits", flush=True)
        cfg = (get_config if args.production else get_reduced)(args.arch)
        prompts = [r.prompt for r in res.requests]
        del res
        gc.collect()
        out["logit_fallback"] = near_tie_check(cfg, prompts, plain, tokens,
                                               differ, args.seal)
    return out, tokens


def tamper_phase(argv, sealed):
    """(d) The bitflip fires and is detected, its victim is re-prefilled,
    and every request the fault did not touch streams exactly the tokens
    of the untampered sealed run ``sealed``. The victim's stream is
    reported against that run."""
    _, res, tokens, out = launch(argv)
    victims = [i for i, r in enumerate(res.requests) if r.retries]
    check(victims, "no request was re-prefilled after the bitflip")
    check(res.stats["mac_failures"] >= 1, "the bitflip went undetected")
    differ = [i for i, (a, b) in enumerate(zip(tokens, sealed))
              if a != b and i not in victims]
    check(not differ, f"requests {differ}, which the fault did not touch, "
                      f"differ from the untampered sealed run")
    out.update(victims=victims, mac_checks=res.stats["mac_checks"],
               mac_failures=res.stats["mac_failures"],
               retries=res.stats["retries"],
               others_equal_to_sealed=len(tokens) - len(victims),
               victims_equal_to_sealed=[tokens[i] == sealed[i]
                                        for i in victims])
    return out


def serving_phases(clock, reduced):
    """(b)-(d): plaintext, sealed and tampered serving, one engine at a
    time (each run's engine is freed before the next one is built)."""
    state = {}

    def plain():
        _, _, state["plain"], out = launch(serve_args(reduced, "--seal",
                                                      "none"))
        return out

    def sealed():
        out, state["sealed"] = sealed_phase(serve_args(reduced, *seal_argv),
                                            state["plain"])
        return out

    seal_argv = ("--seal", "coloe", "--seal-cache", "on")
    phase("b_plaintext_serve", clock, plain)
    phase("c_sealed_serve", clock, sealed)
    phase("d_integrity_bitflip", clock, lambda: tamper_phase(
        serve_args(reduced, *seal_argv, "--verify", "--inject-tamper",
                   "bitflip"), state["sealed"]))


def train_losses(cfg, mesh, seq: int, work: Path, tag: str):
    from repro.config import TrainConfig
    from repro.train.loop import train
    log = work / f"{tag}.jsonl"
    tc = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=3,
                     microbatches=2, checkpoint_every=1000,
                     checkpoint_dir=str(work / f"ckpt_{tag}"), seed=SEED)
    params, _, _ = train(cfg, tc, mesh, batch=8, seq=seq, steps=3,
                         log_path=str(log), resume=False)
    recs = [json.loads(line) for line in log.read_text().splitlines()]
    return [r["loss"] for r in recs if "loss" in r], params


def four_chip_phase(clock, reduced):
    """Three sharded training steps on a (2, 2) mesh against one chip."""
    from repro.configs import get_config, get_reduced
    from repro.launch.mesh import make_host_mesh

    cfg = (get_reduced if reduced else get_config)("internlm2_1_8b")
    cfg = cfg.with_(num_layers=4)
    seq = 32 if reduced else 512
    check(len(jax.devices()) >= 4, f"four chips needed: {jax.devices()}")
    work = Path(tempfile.mkdtemp(prefix=".smoke_", dir=ROOT))
    try:
        def sharded():
            losses, params = train_losses(cfg, make_host_mesh(2, 2), seq,
                                          work, "mesh2x2")
            sets = {len(p.sharding.device_set)
                    for p in jax.tree.leaves(params)}
            check(sets == {4}, f"param device sets {sets}")
            return {"losses": losses}

        def single():
            losses, _ = train_losses(cfg, make_host_mesh(1, 1), seq, work,
                                     "mesh1x1")
            return {"losses": losses}

        a = phase("train_mesh_2x2", clock, sharded)["losses"]
        b = phase("train_mesh_1x1", clock, single)["losses"]
        check(len(a) == len(b) == 3, f"losses {a} and {b}")
        rel = [abs(x - y) / abs(y) for x, y in zip(a, b)]
        print(json.dumps({"phase": "train_compare", "rel_diff": rel}),
              flush=True)
        check(max(rel) <= 1e-2, f"2x2 and 1x1 losses differ by {rel}")
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded-training comparison")
    ap.add_argument("--reduced", action="store_true",
                    help="rehearse at the reduced config, on any platform; "
                         "always exits nonzero and never reports ok")
    args = ap.parse_args()
    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not args.reduced:
        sys.exit(f"no TPU: JAX found {jax.devices()[0].platform}")
    cache = compile_cache.enable()
    print(json.dumps({"device": device_facts(), "compile_cache": cache}),
          flush=True)
    clock = CompileClock()
    if args.four_chip:
        four_chip_phase(clock, args.reduced)
    else:
        if not args.reduced:
            phase("a_kernels", clock, kernels_phase)
        serving_phases(clock, args.reduced)
    if args.reduced:
        # the ok line certifies the published widths only
        sys.exit("rehearsal passed; a reduced run reports no result")
    print(json.dumps({"ok": True, "device": device_facts()}))


if __name__ == "__main__":
    main()
