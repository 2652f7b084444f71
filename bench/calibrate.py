"""Readings that set the limit of a cell's correctness check, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 20

For each seed, in one process: the cell's engine serves the cell's traffic
at its own load through the pre-roll and a short window, exactly as a run
of ``bench/run.py`` does; then, with the engine freed, the reference reads
the widest gap of the served tokens (the program's reading) and of the
tokens its float8 control puts first at the same positions (the control's
reading). Both go through the checks of ``bench/run.py`` against the
configuration's limit: the program's must come out correct, the
control's not. One JSON line per seed. The benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

from bench import driver, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = run.spec.load(ROOT, args.workload)
    dev = run.open_chip(cell)
    import jax
    for seed in (int(s) for s in args.seeds.split(",")):
        eng, arrivals = run.prepare(cell, seed, args.seconds)
        mac0 = eng.stats["mac_failures"]
        res = driver.drive(eng, arrivals, float(cell.traffic["preroll_s"]),
                           args.seconds)
        macs = eng.stats["mac_failures"] - mac0
        picked = run.sample(res, seed)
        del eng
        gc.collect()
        jax.clear_caches()
        served, ctl = run.compare(cell, picked, seed, control=True)
        line = {"workload": cell.name, "seed": seed,
                "requests": [len(o) for _, o in picked], "device": dev}
        for who, gaps in (("program", served), ("control", ctl)):
            rows = run.checks(cell, max(gaps), len(gaps), res,
                              macs if who == "program" else 0)
            line[who] = {"correct": all(ok for _, _, ok in rows.values()),
                         "checks": {k: {"value": v, "limit": lim}
                                    for k, (v, lim, _) in rows.items()}}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
