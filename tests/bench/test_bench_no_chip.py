"""``bench/run.py`` refuses to report off a TPU: it exits nonzero and
prints no result line, also in a checkout that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL["name"],
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(p):
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        assert not line.strip().startswith("{"), line


def test_cpu_run_exits_nonzero_without_a_result():
    p = _run(ROOT)
    _no_result(p)
    assert "TPU" in p.stderr


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))
