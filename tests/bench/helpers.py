"""A tiny benchmark tree for CPU tests of the harness.

It holds one cell, ``tiny.chat``: the reduced internlm2 configuration of
the program (2 layers, d_model 64, vocab 256) with 4 slots, plaintext,
under a fast open-loop mix. ``run_tiny`` drives ``bench/run.py``'s whole
run after the look for the chip, with the program's configuration
swapped for its reduced one, and returns the result line.
"""
import io
import json
import sys
import types
from contextlib import redirect_stdout
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "model_id": "internlm2_1_8b", "reference": "dense_gqa",
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 256, "rope_theta": 10000.0, "tie_word_embeddings": False,
    "rms_norm_eps": 1e-05, "compute_dtype": "bfloat16",
    "slots": 4, "max_len": 128, "seal": "none",
    "correct": {"logit_gap": 0.25},
}
TINY_MIX = {
    "arrival": "poisson", "rate_rps": 6.0, "preroll_s": 0.5,
    "prompt": {"dist": "uniform", "min": 8, "max": 40},
    "output": {"dist": "uniform", "min": 16, "max": 40},
}


def tiny_tree(tmp: Path, config=None, mix=None) -> Path:
    """A root with BENCHMARK.json naming the tiny cell, and the real
    ``bench`` package's files it needs."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    (tmp / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(config or TINY_CONFIG))
    (tmp / "bench" / "traffic" / "tiny_mix.json").write_text(
        json.dumps(mix or TINY_MIX))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                      "file": "bench/configs/tiny.json", "why": "test"}]
    bm["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                        "traffic": "tiny_mix", "chips": 1, "why": "test"}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp


def run_tiny(tmp: Path, monkeypatch, seed=3, seconds=2.0, config=None,
             mix=None, build=None) -> dict:
    """One run of the tiny cell; ``build`` replaces the system's builder
    (to plant a fault under the timed path)."""
    from bench import run, spec, system
    from repro.configs import get_reduced
    root = tiny_tree(tmp, config, mix)
    monkeypatch.setattr(system, "model_config",
                        lambda conf: get_reduced(conf["model_id"]))
    if build is not None:
        monkeypatch.setattr(system, "build", build)
    cell = spec.load(root, "tiny.chat")
    args = types.SimpleNamespace(workload="tiny.chat", seed=seed,
                                 seconds=seconds, trace=0)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.execute(cell, args, {"platform": "cpu", "kind": "cpu",
                                      "count": 1})
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    print(out.getvalue(), file=sys.stderr)
    return json.loads(lines[-1])
