"""The system under test: the program's serving engine, built for one cell.

Everything the benchmark takes from the program is here: its model
configuration by id, its jitted initialisation (the weights are made on
the device, in f32 as they are served, from the run's seed), its sealing
configuration and ``ServeEngine``. Scheduler tunables are left at the
engine's defaults.
"""
from __future__ import annotations

from bench.reference import model_key

# configuration file key -> ModelConfig attribute the program must match
WIDTHS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta",
          "tie_word_embeddings": "tie_embeddings", "compute_dtype": "dtype"}


def model_config(config: dict):
    """The program's configuration of the model, checked against every
    size the configuration file states."""
    from repro.configs import get_config
    cfg = get_config(config["model_id"])
    bad = {k: (config[k], getattr(cfg, a)) for k, a in WIDTHS.items()
           if config[k] != getattr(cfg, a)}
    if bad:
        raise ValueError(f"the program's {config['model_id']} differs from "
                         f"the configuration file (file, program): {bad}")
    return cfg


def build(config: dict, seal: str, seed: int):
    """A ``ServeEngine`` over weights made from ``seed``: plaintext for
    ``seal == "none"``; for ``"full"`` the product as sold, ColoE-sealed
    weights at the paper's smart ratio 0.5, a sealed KV cache, and MACs
    verified on every read."""
    from repro.config import SealConfig
    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine

    cfg = model_config(config)
    params = T.init_params(cfg, model_key(seed))
    if seal == "full":
        kw = dict(seal=SealConfig(mode="coloe", smart_ratio=0.5, verify=True),
                  verify=True)
    elif seal == "none":
        kw = {}
    else:
        raise ValueError(f"unknown seal {seal!r}: 'full' or 'none'")
    eng = ServeEngine(cfg, params, batch_slots=config["slots"],
                      max_len=config["max_len"], donate_params=True, **kw)
    del params
    return eng


def warm_up(eng, vocab: int):
    """Drive admit, chunk step, decode tick and evict with two short
    requests, twice: the second round must find every program compiled."""
    import numpy as np
    rng = np.random.default_rng(0)
    for _ in range(2):
        for _ in range(2):
            eng.submit(rng.integers(0, vocab, 40, dtype=np.int32),
                       max_tokens=3)
        while eng.busy:
            eng.step()
