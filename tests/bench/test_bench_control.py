"""The control of the correctness check: the plain reference put in the
program's place and computed in float8 (the precision below the
configuration's bf16) must come out as not correct, while the program's
own bf16 path comes out correct (``test_bench_harness.py``)."""
import types

import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.refs import dense_gqa
from helpers import run_tiny


class Fp8Server:
    """Serves greedy tokens from the float8 reference, with the engine's
    public surface: ``submit``, ``step``, ``busy``, ``queue``, ``stats``."""

    def __init__(self, config, seed):
        self.spec = reference.spec_tuple(config)
        self.key = reference.model_key(seed)
        self.slots, self.max_len = config["slots"], config["max_len"]
        self.queue, self.active = [], []
        self.stats = {"mac_failures": 0}

    def submit(self, prompt, max_tokens=32):
        r = types.SimpleNamespace(prompt=np.asarray(prompt, np.int32),
                                  max_tokens=max_tokens, out=[], done=False,
                                  error=None)
        self.queue.append(r)
        return r

    @property
    def busy(self):
        return bool(self.queue or self.active)

    def step(self):
        while self.queue and len(self.active) < self.slots:
            self.active.append(self.queue.pop(0))
        toks = np.zeros((self.slots, self.max_len), np.int32)
        pos = np.zeros((self.slots, 1), np.int32)
        for i, r in enumerate(self.active):
            s = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
            toks[i, :len(s)] = s
            pos[i, 0] = len(s) - 1
        logits = dense_gqa.logits_at(self.spec, self.key, jnp.asarray(toks),
                                     jnp.asarray(pos), quant="fp8")
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
        for i, r in enumerate(self.active):
            r.out.append(int(nxt[i]))
            r.done = len(r.out) >= r.max_tokens
        self.active = [r for r in self.active if not r.done]
        return []


def test_fp8_reference_in_the_programs_place_is_not_correct(
        tmp_path, monkeypatch):
    res = run_tiny(tmp_path, monkeypatch,
                   build=lambda config, seal, seed: Fp8Server(config, seed))
    assert not res["correct"]
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
