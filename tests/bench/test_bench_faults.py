"""The check that decides ``correct`` catches a broken timed path.

Each test drives the harness's whole run on the CPU at a tiny size with a
fault planted under the timed path, where the program produces it, and
sees ``correct`` come out false; the clean run beside them comes out true
(``test_bench_harness.py``)."""
import jax
import pytest

from bench import system
from helpers import TINY_CONFIG, run_tiny

REAL_BUILD = system.build


def _altered_token(config, seal, seed):
    """The decode tick serves slot 0 a token other than the one it
    sampled."""
    eng = REAL_BUILD(config, seal, seed)
    tick = eng._decode

    def altered(*args):
        tok, cok, state, pools = tick(*args)
        tok = tok.at[0].set((tok[0] + 1) % TINY_CONFIG["vocab_size"])
        return tok, cok, state, pools
    eng._decode = altered
    return eng


def _state_unchanged(config, seal, seed):
    """The decode tick returns the KV cache it was given: the tokens it
    decodes are never written back."""
    eng = REAL_BUILD(config, seal, seed)
    fn = eng._decode_fn

    def frozen(params, pools, state):
        tok, cok, state, _ = fn(params, pools, state)
        return tok, cok, state, pools
    eng._decode = jax.jit(frozen)
    return eng


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["token_altered", "cache_state_unchanged"])
def test_fault_makes_the_run_incorrect(tmp_path, monkeypatch, fault):
    res = run_tiny(tmp_path, monkeypatch, build=fault)
    assert not res["correct"]
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
