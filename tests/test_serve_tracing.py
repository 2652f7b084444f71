"""Instrumentation of the serving path.

Host spans: ``ServeEngine.step`` leaves ``serve.*`` spans on the profiler's
trace, and ``serve.decode`` carries the cache read's block counters, taken
from the host mirrors: the paged attention kernel walks exactly the running
slots' resident blocks, out of the whole tables a dense view would gather.
Device scopes: the tick's operations carry the ``paged_attention``,
``kv_append`` and ``weight_decrypt`` scopes in their ``op_name`` metadata,
and the scopes leave the optimised program unchanged.
(The dense view's ``kv_view`` scope and the ``kv_gather``, ``kv_mac``,
``kv_unseal`` and ``kv_mask`` scopes inside it now name only MLA's and
sliding-window layers' reads: ``tests/test_moonlight_model.py::
test_moe_and_mla_scopes_name_operations_and_change_none`` checks them.)
"""
import contextlib
import glob
import re

import jax
import numpy as np
import pytest

from repro.config import SealConfig
from repro.configs import get_reduced
from repro.models import transformer as T
from repro.serve.engine import ServeEngine

SPANS = ("serve.step", "serve.admit", "serve.chunk", "serve.chunk.readback",
         "serve.decode", "serve.decode.readback", "serve.integrity",
         "serve.evict", "serve.verify_weights")
COUNTERS = ("blocks_gathered", "blocks_walked", "blocks_resident",
            "blocks_reserved", "running")


def _engine():
    """Verified, direct-sealed weights over a sealed cache: every span and
    scope has work to cover, and the graphs compile in seconds."""
    cfg = get_reduced("internlm2_1_8b")
    params = T.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48,
                      seal=SealConfig(mode="direct", smart_ratio=1.0),
                      seal_cache=True, verify=True, sample_seed=5)
    rng = np.random.RandomState(7)
    for n in (11, 20, 7):
        eng.submit(rng.randint(1, cfg.vocab_size, (n,)), max_tokens=5)
    return eng


def _expected_counters(eng):
    """The decode counters from the host mirrors, before the tick: the
    kernel walks the running slots' resident blocks and no others."""
    bs = eng.block_size
    running = [i for i, r in enumerate(eng._active)
               if r is not None and eng._pending[i] is None]
    resident = sum(-(-int(eng._lengths[i]) // bs) for i in running)
    return {"blocks_gathered": eng.slots * eng.max_len // bs,
            "blocks_walked": resident,
            "blocks_resident": resident,
            "blocks_reserved": sum(len(b) for b in eng._slot_blocks),
            "running": len(running)}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The engine served to the end under the profiler: (engine, its
    ``serve.*`` host events, the counters the mirrors gave each tick)."""
    from jax.profiler import ProfileData
    eng = _engine()
    expected = []
    decode = eng._decode

    def watched(*args):
        expected.append(_expected_counters(eng))
        return decode(*args)

    eng._decode = watched
    tdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(tdir)):
        while eng.busy:
            eng.step()
    eng._decode = decode
    path = glob.glob(str(tdir / "**" / "*.xplane.pb"), recursive=True)[0]
    events = [e for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:") for line in plane.lines
              for e in line.events if e.name.startswith("serve.")]
    return eng, events, expected


def test_step_writes_every_serve_span_with_the_mirrors_counters(traced):
    eng, events, expected = traced
    assert set(SPANS) <= {e.name for e in events}
    decodes = sorted((e for e in events if e.name == "serve.decode"),
                     key=lambda e: e.start_ns)
    got = [{k: v for k, v in e.stats if k in COUNTERS} for e in decodes]
    assert got == expected and len(got) == eng.stats["decode_steps"]
    # the stats carry the same counters, summed over the ticks
    for k in ("gathered", "walked", "resident", "reserved"):
        assert eng.stats[f"kv_blocks_{k}"] == sum(
            c[f"blocks_{k}"] for c in expected)
    assert 0 < eng.stats["kv_blocks_walked"] == (
        eng.stats["kv_blocks_resident"]) <= (
        eng.stats["kv_blocks_reserved"]) < eng.stats["kv_blocks_gathered"]
    # readbacks nest inside their dispatch's span, phases inside the step
    steps = [(e.start_ns, e.end_ns) for e in events if e.name == "serve.step"]
    for e in events:
        if e.name in ("serve.chunk.readback", "serve.decode.readback"):
            parent = e.name.rsplit(".", 1)[0]
            assert any(p.start_ns <= e.start_ns and e.end_ns <= p.end_ns
                       for p in events if p.name == parent)
        if e.name in ("serve.admit", "serve.chunk", "serve.decode"):
            assert any(a <= e.start_ns and e.end_ns <= b for a, b in steps)


def _program(text: str) -> str:
    """Optimised HLO text without its source metadata (the ``metadata``
    attributes and the module's tables of files and stack frames), with
    every value renamed by its first appearance: a name stack may lend a
    value its name, which changes no operation."""
    text = re.sub(r",?\s*metadata=\{[^}]*\}", "", text)
    text = text[text.index("\n%"):]
    text = re.sub(r"([(,] ?)([\w.\-]+): ", r"\1%\2: ", text)
    names = {}
    return re.sub(r"%([\w.\-]+)",
                  lambda m: f"%v{names.setdefault(m.group(1), len(names))}",
                  text)


def test_scopes_name_the_ticks_operations_and_change_no_operation(
        traced, monkeypatch):
    eng = traced[0]
    args = eng._decode_args()

    def compiled():
        # a fresh function: nothing is reused from an earlier trace
        return jax.jit(lambda *a: eng._decode_fn(*a)).lower(*args).compile()

    scoped = compiled().as_text()
    ops = re.findall(r'op_name="([^"]*)"', scoped)
    for scope in ("paged_attention", "kv_append", "weight_decrypt",
                  "sampling"):
        assert any(f"/{scope}/" in o for o in ops), scope
    assert not any("/kv_view/" in o for o in ops)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled().as_text()
    assert "/paged_attention/" not in plain and "weight_decrypt" not in plain
    assert _program(scoped) == _program(plain)


def test_compiled_hlo_gives_both_programs_with_their_scopes(traced):
    texts = traced[0].compiled_hlo()
    assert set(texts) == {"tick", "chunk_step"}
    for prog, text in texts.items():
        ops = re.findall(r'op_name="([^"]*)"', text)
        for scope in ("paged_attention", "kv_append", "sampling"):
            assert any(f"/{scope}/" in o for o in ops), (prog, scope)
