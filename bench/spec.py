"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) is a configuration under a traffic mix.
A configuration's file is the one its entry in ``configs`` names: the
model, the deployment (slots, length, sealing) and the limits of the
correctness check. A mix is ``bench/traffic/<mix>.json``, with its rate,
and a per-layer metric is read by ``bench/metrics/<metric>.py``. Adding a cell,
a configuration, a mix or a metric adds files and entries; no file that is
already here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                 # the configuration's file
    traffic: dict                # the mix's file
    end_to_end: List[dict]       # the metrics this cell reports
    per_layer: List[dict]
    bench_dir: Path

    def reader(self, metric: str) -> Callable:
        """The per-layer metric's reader: ``read(ctx)`` from
        ``metrics/<metric>.py``."""
        path = self.bench_dir / "metrics" / f"{metric}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(root: Path, name: str, bench_dir: Path = None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``."""
    root = Path(root)
    bench_dir = Path(bench_dir) if bench_dir else root / "bench"
    bm = _json(root / "BENCHMARK.json")
    cells: Dict[str, dict] = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(root / configs[w["config"]]["file"]),
        traffic=_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)
