"""Serve benchmark: continuous batching vs the group-drain baseline.

Replays one Poisson arrival trace with a long-tailed output-length mix
(80% short 4-8 tokens, 20% long 40-64) through both schedulers and writes
``BENCH_serve.json``. Each engine first runs the identical trace once to
warm every jit shape; that warmup wall time is recorded separately as
``compile_s`` and the timed pass — bracketed by ``block_until_ready`` on
live device state so no async dispatch leaks across the timer — measures
steady-state tokens/s and per-request latency.

The headline comparison runs both engines plaintext so the delta is pure
scheduling: group-drain burns decode steps on drained slots while the
continuous batcher refills them. A third timed pass runs the continuous
engine with the **sealed** paged KV cache to price the cache sealing, and
its stats show ``kv_plaintext_bytes_per_step`` dropping to 0. A fourth
pass (``continuous_sealed_verified``) arms the co-located Carter–Wegman
MACs on top of the sealed cache — verified on every gather, re-minted on
every append — and ``verify_overhead_x`` prices that integrity layer
against the seal-only run. A slots sweep (default 16/64/256, load scaled
with the slot count) tracks the ROADMAP's throughput trajectory for the
device-resident scheduler.
"""
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.config import SealConfig
from repro.configs import get_reduced
from repro.launch.serve import drive, poisson_arrivals
from repro.models import transformer as T
from repro.runtime import compile_cache
from repro.serve.engine import GroupServeEngine, ServeEngine

MAX_LEN = 96


def make_trace(cfg, requests: int, seed: int, mean_gap: float):
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, size=rng.randint(4, 25))
               for _ in range(requests)]
    long_tail = rng.rand(requests) < 0.2
    max_toks = np.where(long_tail, rng.randint(40, 65, size=requests),
                        rng.randint(4, 9, size=requests))
    arrivals = poisson_arrivals(requests, mean_gap, rng)
    kws = [dict(max_tokens=int(mt)) for mt in max_toks]
    return prompts, kws, arrivals


def _sync(eng):
    """Block until the engine's outstanding device work has retired, so a
    wall-clock reading brackets exactly the work issued so far."""
    state = getattr(eng, "_state", None)
    if state is not None:
        jax.block_until_ready(state)
    pools = getattr(eng, "_pools", None)
    if pools is not None:
        jax.block_until_ready(pools)


def bench_engine(eng, prompts, kws, arrivals):
    t0 = time.time()
    drive(eng, prompts, arrivals, kws)            # warm every jit shape
    _sync(eng)
    compile_s = time.time() - t0                  # compile + first replay
    tok0, ds0, pf0 = (eng.stats["tokens"], eng.stats["decode_steps"],
                      eng.stats["prefills"])
    mc0 = eng.stats.get("mac_checks", 0)
    t0 = time.time()
    reqs = drive(eng, prompts, arrivals, kws)
    _sync(eng)
    wall = time.time() - t0
    lat = np.array([r.t_done - r.t_submit for r in reqs])
    tokens = eng.stats["tokens"] - tok0
    return {
        "requests": len(reqs),
        "completed": int(sum(r.done for r in reqs)),
        "tokens": int(tokens),
        "decode_steps": eng.stats["decode_steps"] - ds0,
        "prefills": eng.stats["prefills"] - pf0,
        "compile_s": round(compile_s, 3),
        "wall_s": round(wall, 3),
        "tokens_per_s": round(tokens / max(wall, 1e-9), 1),
        "latency_p50_s": round(float(np.percentile(lat, 50)), 4),
        "latency_p99_s": round(float(np.percentile(lat, 99)), 4),
        "plaintext_bytes_per_step": int(eng.stats["plaintext_bytes_per_step"]),
        **{k: int(eng.stats[k]) for k in
           ("weights_plaintext_bytes_per_step", "kv_plaintext_bytes_per_step",
            "prefill_chunks", "shared_prefix_blocks", "cow_copies",
            "mac_failures", "retries")
           if k in eng.stats},
        **({"mac_checks": int(eng.stats["mac_checks"] - mc0)}
           if getattr(eng, "verify", False) else {}),
    }


def _bench_cfg(arch: str):
    # Scale the reduced config up until per-step compute dominates host
    # dispatch — at toy sizes the scheduler comparison measures Python
    # overhead, not scheduling. f32: CPU bf16 is emulated and ~2x slower.
    return get_reduced(arch).with_(
        d_model=512, num_heads=8, num_kv_heads=4, head_dim=64, d_ff=2048,
        num_layers=6, dtype="float32")


def serve_bench(arch: str = "internlm2_1_8b", requests: int = 48,
                slots: int = 16, seed: int = 0, mean_gap: float = 2.0,
                sweep_slots=(16, 64, 256), out_path: str = "BENCH_serve.json"):
    cfg = _bench_cfg(arch)
    params = T.init_params(cfg, jax.random.key(0))
    prompts, kws, arrivals = make_trace(cfg, requests, seed, mean_gap)

    def run_one(make):
        # engines own pool-sized device buffers; drop each before building
        # the next so a 6-engine run doesn't accumulate dead pools (memory
        # pressure skews the later sweep points)
        eng = make()
        rec = bench_engine(eng, prompts, kws, arrivals)
        del eng
        gc.collect()
        return rec

    rec_cont = run_one(lambda: ServeEngine(
        cfg, params, batch_slots=slots, max_len=MAX_LEN, seal=None,
        seal_cache=False, sample_seed=seed, admit_batch=2))
    rec_grp = run_one(lambda: GroupServeEngine(
        cfg, params, batch_slots=slots, max_len=MAX_LEN))
    rec_sealed = run_one(lambda: ServeEngine(
        cfg, params, batch_slots=slots, max_len=MAX_LEN, seal=None,
        seal_cache=True, sample_seed=seed, admit_batch=2))
    # price the integrity layer: same sealed cache, per-block Carter-Wegman
    # MACs verified at every gather and re-minted at every append
    rec_verified = run_one(lambda: ServeEngine(
        cfg, params, batch_slots=slots, max_len=MAX_LEN, seal=None,
        seal_cache=True, sample_seed=seed, admit_batch=2, verify=True))

    # slots sweep: measure serving *capacity* — 3 requests per slot with
    # the Poisson arrival rate scaled to keep every point near saturation
    # (a decode tick costs the same whether 5 or 60 of the slots are live,
    # so an under-driven point measures idle-slot overhead, not
    # throughput; a fixed-rate trace would leave a 256-slot engine ~3%
    # occupied). gap = mean_gap * 8 / ns holds per-slot load at 2x the
    # headline trace's, which keeps the measured occupancy comparable
    # (~85%) across the sweep.
    sweep = {}
    for ns in sweep_slots or ():
        sp, skw, sar = make_trace(cfg, 3 * ns, seed, mean_gap * 8.0 / ns)
        eng = ServeEngine(cfg, params, batch_slots=ns, max_len=MAX_LEN,
                          seal=None, seal_cache=False, sample_seed=seed,
                          admit_batch=max(2, ns // 8), prefix_share=True)
        sweep[str(ns)] = bench_engine(eng, sp, skw, sar)
        del eng
        gc.collect()

    speedup = rec_cont["tokens_per_s"] / max(rec_grp["tokens_per_s"], 1e-9)
    verify_overhead = (rec_sealed["tokens_per_s"]
                       / max(rec_verified["tokens_per_s"], 1e-9))
    result = {
        "arch": arch, "slots": slots, "requests": requests, "seed": seed,
        "trace": {"arrival": "poisson", "mean_gap_steps": mean_gap,
                  "prompt_len": [4, 24], "short_tokens": [4, 8],
                  "long_tokens": [40, 64], "long_frac": 0.2},
        "continuous": rec_cont,
        "group_drain": rec_grp,
        "continuous_sealed_cache": rec_sealed,
        "continuous_sealed_verified": rec_verified,
        "slots_sweep": sweep,
        "speedup_tokens_per_s": round(speedup, 2),
        "speedup_ok": bool(speedup >= 1.3),
        "verify_overhead_x": round(verify_overhead, 3),
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main(sweep_slots=None):
    compile_cache.enable()
    res = serve_bench(**({} if sweep_slots is None
                         else {"sweep_slots": sweep_slots}))
    print(json.dumps(res, indent=1))
    tag = "PASS" if res["speedup_ok"] else "FAIL"
    print(f"{tag}: continuous vs group-drain speedup "
          f"{res['speedup_tokens_per_s']}x (target >= 1.3x)")
    print(f"integrity verification overhead: {res['verify_overhead_x']}x "
          f"over the sealed cache "
          f"({res['continuous_sealed_verified']['mac_checks']} MAC checks, "
          f"{res['continuous_sealed_verified']['mac_failures']} failures)")


if __name__ == "__main__":
    main()
