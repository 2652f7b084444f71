"""Finds the highest rate a chat cell sustains, once, by a sweep on the chip.

    python3 bench/sweep.py --workload <cell> --rates 0.6,0.8,1.0 --seconds 30

One engine serves the cell's mix at each rate in turn (pre-roll, then the
window), with the arrivals of that rate; between rates the queue is
dropped and the requests in flight finish. One JSON line per rate: time to
first token, tokens per second against what the rate offers, and the queue
left at the window's end. A rate is sustained while tokens per second keep
up with it and no backlog grows; the cell then runs at about 0.8 of the
highest such rate. The benchmark's runs never search for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import driver, latency, run, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = run.spec.load(ROOT, args.workload)
    dev = run.open_chip(cell)
    conf, mix = cell.config, cell.traffic
    eng, _ = run.prepare(cell, args.seed, args.seconds)
    mean_out = float(np.mean(traffic.lengths(mix["output"], 1000)))
    for rate in (float(r) for r in args.rates.split(",")):
        arrivals = traffic.schedule(mix, rate, args.seconds, args.seed,
                                    conf["vocab_size"])
        res = driver.drive(eng, arrivals, float(mix["preroll_s"]),
                           args.seconds)
        w0, w1 = res.w0, res.w1
        print(json.dumps({
            "workload": cell.name, "rate_rps": rate,
            "ttft_p50_ms": 1e3 * latency.percentile(
                latency.ttfts(res.recs, w0, w1), 50),
            "ttft_p90_ms": 1e3 * latency.percentile(
                latency.ttfts(res.recs, w0, w1), 90),
            "itl_p95_ms": 1e3 * latency.percentile(
                latency.itls(res.recs, w0, w1), 95),
            "output_tok_s": latency.rate(res.recs, w0, w1),
            "offered_tok_s": rate * mean_out,
            "queued_at_end": len(eng.queue),
            "in_flight_at_window_start": res.in_flight_at_w0,
            "steps": len(res.steps),
            "step_s_mean": float(np.mean([s.end - s.start
                                          for s in res.steps])),
            "decoding_per_step": [
                [round(s.start - w0, 1), len(s.decode_contexts)]
                for s in res.steps[::max(1, len(res.steps) // 40)]],
            "device": dev}), flush=True)
        eng.queue.clear()
        while eng.busy:
            eng.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
