"""Runs one cell of the chip benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (model, slots, sealing) and a traffic mix (arrivals, rate,
lengths), each a file that ``bench/spec.py`` finds by name. One process builds the serving engine over
weights made from the seed, warms up every program the cell uses, runs the
mix's pre-roll and then measures for ``--seconds``. With ``--trace 1`` it
records the last seconds of the window with the profiler and reports the
cell's per-layer metrics; otherwise its end-to-end metrics. Once the window
has closed and the engine is freed, a plain reference checks a sample of
the served tokens (``bench/reference.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, each number compared beside its limit;
the same checks are the last lines of standard error. Off a TPU, or with
fewer chips than the cell asks for, it exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import driver, latency, reference, roofline, spec, traffic  # noqa: E402
from bench import trace as TR  # noqa: E402

TRACE_SECONDS = 8.0      # the traced part: the window's last seconds
SAMPLE = 12              # requests the reference checks, the longest among them
MIN_COMPARED = 100       # served tokens a sound check compares at least


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def device_facts(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def log(**kw):
    print(json.dumps(kw), file=sys.stderr, flush=True)


class CompileCounter:
    """Counts the programs JAX compiles, from its monitoring events."""

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def sample(run: driver.Run, seed: int, k: int = SAMPLE):
    """Up to ``k`` served streams drawn from the seed: the longest finished
    request, then other finished ones; where too few finished in the run,
    requests still in flight fill up with the tokens they were served."""
    fin = [r for r in run.requests if r.done and r.error is None and r.out]
    fly = [r for r in run.requests if not r.done and len(r.out) >= 2]
    rng = np.random.default_rng([seed, 1])
    picked = []
    if fin:
        longest = max(fin, key=lambda r: len(r.prompt) + len(r.out))
        rest = [r for r in fin if r is not longest]
        picked = [longest] + [rest[i] for i in
                              rng.permutation(len(rest))[:k - 1]]
    if len(picked) < k and fly:
        picked += [fly[i] for i in rng.permutation(len(fly))[:k - len(picked)]]
    return [(np.asarray(r.prompt, np.int32), list(r.out)) for r in picked]


def end_to_end(cell, run: driver.Run, setup_s: float) -> dict:
    w0, w1 = run.w0, run.w1
    values = {
        "itl_p95_ms": latency.percentile(latency.itls(run.recs, w0, w1), 95),
        "output_tok_s": latency.rate(run.recs, w0, w1),
        "setup_s": setup_s,
    }
    out = {}
    for m in cell.end_to_end:
        v = values.get(m["name"])
        if v is None:
            continue
        scale = 1e3 if m["unit"] == "ms" else 1.0
        out[m["name"]] = {"value": v * scale, "unit": m["unit"]}
    return out


def per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        v = cell.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def checks(cell, gap, compared, run, mac_failures) -> dict:
    """Every number compared, with its limit: (value, limit, passes)."""
    limit = cell.config["correct"]["logit_gap"]
    errored = sum(1 for r in run.requests if r.error is not None)
    short = sum(1 for r in run.requests
                if r.done and r.error is None
                and len(r.out) != r.max_tokens)
    rows = {
        "logit_gap": (gap, limit,
                      gap is not None and limit is not None and gap <= limit),
        "compared_tokens": (compared, MIN_COMPARED, compared >= MIN_COMPARED),
        "errored_requests": (errored, 0, errored == 0),
        "mac_failures": (mac_failures, 0, mac_failures == 0),
        "short_streams": (short, 0, short == 0),
    }
    return rows


def open_chip(cell) -> dict:
    """The device facts, once JAX has found the chips the cell asks for;
    off a TPU, or with fewer chips, the process ends without a result. The
    compile cache is the program's fixed one inside the checkout."""
    import jax
    dev = device_facts(jax)
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        sys.exit(f"no result: the cell needs {cell.chips} TPU chip(s); JAX "
                 f"found {dev['count']} {dev['platform']} device(s)")
    from repro.runtime import compile_cache
    dev["compile_cache"] = compile_cache.enable()
    # every program, however quick to compile, is kept: a later run of the
    # cell then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return dev


def prepare(cell, seed: int, seconds: float):
    """The set-up of one run, shared by every tool that drives a cell: the
    schedule drawn from the seed, and the engine over weights made from the
    seed, with every program the cell uses warmed up."""
    from bench import system
    conf = cell.config
    arrivals = traffic.schedule(cell.traffic, cell.traffic.get("rate_rps", 0),
                                seconds, seed, conf["vocab_size"])
    eng = system.build(conf, conf["seal"], seed)
    system.warm_up(eng, conf["vocab_size"])
    return eng, arrivals


def compare(cell, picked, seed: int, control: bool = False):
    """Gaps of the served tokens against the plain reference (and, with
    ``control``, of the reference's float8 control at the same positions);
    run once the engine is freed."""
    conf = cell.config
    width = -(-int(cell.traffic["output"]["max"]) // 128) * 128
    return reference.gaps(conf, seed, picked, conf["max_len"], width,
                          control=control)


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load(ROOT, args.workload)
    dev = open_chip(cell)
    log(device=dev, workload=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace)
    dev.pop("compile_cache")
    return execute(cell, args, dev)


def execute(cell, args, dev) -> int:
    """Everything of a run after the look for the chip: set-up, pre-roll,
    window, metrics, the check against the reference and the result."""
    import jax
    compiles = CompileCounter(jax)
    conf = cell.config
    eng, arrivals = prepare(cell, args.seed, args.seconds)
    mac0 = eng.stats["mac_failures"]
    preroll = float(cell.traffic["preroll_s"])
    setup_s = time.perf_counter() - T_START + preroll
    compiled_before = compiles.n

    tdir, tracer = None, None
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        ann = jax.profiler.TraceAnnotation("bench.traced")

        def start():
            jax.profiler.start_trace(tdir)
            ann.__enter__()

        def stop():
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        tracer = (TRACE_SECONDS, start, stop)
    run = driver.drive(eng, arrivals, preroll, args.seconds,
                       span=jax.profiler.TraceAnnotation, trace=tracer)
    in_window = compiles.n - compiled_before
    lateness = np.asarray(run.lateness) if run.lateness else np.zeros(1)
    log(generator_late_s={"p50": float(np.median(lateness)),
                          "max": float(lateness.max())},
        requests_submitted=len(run.requests),
        in_flight_at_window_start=run.in_flight_at_w0,
        steps=len(run.steps), compiles_in_window=in_window,
        decoding_at_window_start=next(
            (len(s.decode_contexts) for s in run.steps if s.start >= run.w0),
            None),
        stats={k: v for k, v in eng.stats.items()
               if isinstance(v, (int, float))})
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    device = dict(dev, memory_peak_bytes=mem)
    mac_failures = eng.stats["mac_failures"] - mac0

    metrics, breakdown = {}, None
    if args.trace:
        summary = TR.summarize(TR.extract(TR.find_xplane(tdir)))
        shutil.rmtree(tdir, ignore_errors=True)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        ctx = types.SimpleNamespace(run=run, trace=summary, config=conf,
                                    peak=roofline.peaks(dev["kind"]))
        metrics = per_layer(cell, ctx)
        breakdown = summary.breakdown()
    else:
        metrics = end_to_end(cell, run, setup_s)

    picked = sample(run, args.seed)
    del eng
    gc.collect()
    jax.clear_caches()
    served, _ = compare(cell, picked, args.seed)
    rows = checks(cell, max(served) if served else None, len(served), run,
                  mac_failures)
    correct = all(ok for _, _, ok in rows.values())
    asked = latency.served_in(run.recs, run.w0, run.w1)
    result = {"correct": correct, "attempted": len(asked),
              "failed": sum(1 for r in asked if r.error is not None),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim, _) in rows.items()}
    for k, (v, lim, ok) in rows.items():
        print(f"check {k}: {v} limit {lim} {'ok' if ok else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
