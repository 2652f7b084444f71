"""HLO analyzer correctness (loop-trip scaling vs analytic FLOPs) and a
subprocess mini dry-run (8 forced host devices — isolated so the main test
process keeps its single CPU device)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_stats as H

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_parser_counts_scan_trips():
    """A scanned matmul must count trips x body flops (cost_analysis does
    not — that's the whole reason this parser exists)."""
    w = jnp.zeros((64, 64), jnp.float32)

    def f(x):
        def body(c, _):
            return c @ w, 0
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    txt = jax.jit(f).lower(jnp.zeros((8, 64), jnp.float32)).compile().as_text()
    stats = H.module_totals(txt)
    expect = 10 * 2 * 8 * 64 * 64
    assert abs(stats["flops"] - expect) / expect < 0.05


def test_parser_nested_scans():
    w = jnp.zeros((32, 32), jnp.float32)

    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, 0
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, 0
        out, _ = jax.lax.scan(outer, x, None, length=3)
        return out

    txt = jax.jit(f).lower(jnp.zeros((4, 32), jnp.float32)).compile().as_text()
    stats = H.module_totals(txt)
    expect = 3 * 4 * 2 * 4 * 32 * 32
    assert abs(stats["flops"] - expect) / expect < 0.1


def test_parser_flops_match_6nd():
    """Full train step vs analytic 6ND (+attention+remat) on a small model."""
    from repro.configs import get_reduced
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.train.step import make_train_step
    from repro.config import TrainConfig

    cfg = get_reduced("internlm2_1_8b").with_(num_layers=4)
    tc = TrainConfig(microbatches=1, remat="none")
    step = make_train_step(cfg, tc)
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0)))
    opt = jax.eval_shape(adamw.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32),
             "targets": jax.ShapeDtypeStruct((4, 64), jnp.int32)}
    txt = jax.jit(step).lower(params, opt, batch).compile().as_text()
    stats = H.module_totals(txt)
    n = cfg.param_count()
    toks = 4 * 64
    lo, hi = 6 * n * toks, 6 * n * toks * 2.2  # attention + opt overheads
    assert lo * 0.8 <= stats["flops"] <= hi, (stats["flops"], lo, hi)


def test_parser_collectives_nonzero_on_sharded_matmul():
    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    # single device: no collectives expected — the parser must return {}
    txt = jax.jit(lambda x: x @ x).lower(
        jnp.zeros((64, 64), jnp.float32)).compile().as_text()
    stats = H.module_totals(txt)
    assert stats["collectives"] == {}


@pytest.mark.slow
def test_mini_dryrun_subprocess(tmp_path):
    """End-to-end dry-run machinery on a forced-8-device subprocess."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {SRC!r})
import jax, jax.numpy as jnp, json
from repro.configs import get_reduced
from repro.config import TrainConfig
from repro.models import transformer as T
from repro.optim import adamw
from repro.sharding import rules
from repro.sharding.api import use_mesh
from repro.train.step import make_train_step

cfg = get_reduced("qwen3_moe_30b_a3b").with_(num_layers=4)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     (jax.sharding.AxisType.Auto,) * 2)
tc = TrainConfig(microbatches=2, remat="full")
step = make_train_step(cfg, tc)
pspec = T.param_spec(cfg)
ospec = jax.eval_shape(adamw.init, pspec)
p_sh = rules.to_named(mesh, rules.param_pspecs(cfg, mesh))
o_sh = rules.to_named(mesh, rules.opt_pspecs(cfg, mesh))
b_sh = rules.to_named(mesh, rules.batch_pspecs(cfg, mesh, "train"))
batch = {{"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
         "targets": jax.ShapeDtypeStruct((8, 32), jnp.int32)}}
with use_mesh(mesh, rules.arch_rules(cfg, mesh)):
    c = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                donate_argnums=(0, 1)).lower(pspec, ospec, batch).compile()
ma = c.memory_analysis()
ca = c.cost_analysis()
ca = ca[0] if isinstance(ca, (list, tuple)) else ca   # jax<0.4.35 returns a list
print(json.dumps({{"ok": True, "temp": ma.temp_size_in_bytes,
                  "flops": ca["flops"]}}))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["flops"] > 0


def test_sealed_dryrun_fused_variant_lowers():
    """The coloe_fused dry-run step traces on the production mesh (its own
    subprocess forces the host devices), and it reports what the sealed
    store itself reports: the same fused leaves, and the same per-step
    plaintext bytes, the tile-stored embedding included."""
    code = f"""
import sys
sys.path.insert(0, {SRC!r})
import json
from repro.launch import sealed_dryrun as SD
_, rec = SD.lower_sealed_decode("internlm2_1_8b", "decode_32k",
                                "coloe_fused", reduced=True)
print(json.dumps(rec))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])

    from repro.config import SealConfig
    from repro.configs import get_reduced
    from repro.core import sealed_store as SS
    from repro.models import transformer as T
    cfg = get_reduced("internlm2_1_8b")
    params = T.init_params(cfg, jax.random.key(0))
    sp = SS.seal_params(params, SealConfig(mode="coloe", smart_ratio=0.5),
                        bytes(range(32)))
    assert sp.tensors["embed/w"].meta.layout == "tiles"
    assert "embed/w" not in sp.fused_paths()
    assert rec["fused_matmul_leaves"] == len(sp.fused_paths()) > 0
    assert (rec["plaintext_bytes_materialized_per_step"]
            == sp.plaintext_bytes_materialized()
            >= sp.tensors["embed/w"].logical_bytes())
