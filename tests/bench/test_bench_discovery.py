"""Cells, configurations, mixes and per-layer metrics are found by name:
adding one adds files and entries, and edits no file that exists."""
import json
import shutil
import types
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in BM["workloads"]])
def test_every_cell_finds_its_files(name):
    cell = spec.load(ROOT, name)
    assert cell.config["seal"] in ("full", "none")
    assert cell.traffic["arrival"] in ("poisson", "backlog")
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]))
        # a per-layer metric's cells report the end-to-end metric it moves
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_added_files_and_entries_are_found_without_edits(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    conf = json.loads((bench / "configs" / "internlm2_1_8b.json").read_text())
    conf.update(slots=8, seal="none")
    (bench / "configs" / "new_model.json").write_text(json.dumps(conf))
    (bench / "traffic" / "new_mix.json").write_text(json.dumps(
        {"arrival": "poisson", "rate_rps": 3.0, "preroll_s": 5,
         "prompt": {"dist": "uniform", "min": 8, "max": 16},
         "output": {"dist": "uniform", "min": 8, "max": 16}}))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.x\n")
    bm = json.loads(json.dumps(BM))
    bm["configs"].append({"name": "new_model", "source": "test",
                          "file": "bench/configs/new_model.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "new_model.new_mix",
                            "config": "new_model", "traffic": "new_mix",
                            "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "new_metric", "unit": "ms",
                            "better": "lower", "source": "host_clock",
                            "layer": "host scheduler", "moves": "itl_p95_ms",
                            "workloads": ["new_model.new_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = spec.load(tmp_path, "new_model.new_mix")
    assert cell.config["slots"] == 8 and cell.traffic["rate_rps"] == 3.0
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert cell.reader("new_metric")(types.SimpleNamespace(x=1.5)) == 3.0
    assert {p: p.read_bytes() for p in before} == before
