"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, the plain
reference named by the configuration (``bench/refs/<name>.py``) runs once,
teacher-forced, over each sampled request's prompt and served tokens. At
every served position it reads how far the served token's logit lies below
the reference's best logit there; the widest such gap over the sample is
the number compared. Greedy decoding serves the best token of the program's
own logits, so the gap is 0 unless the program's logits departed from the
reference's, and a near-tie it broke the other way reads small.

``control_gap`` reads the same positions for the reference's float8
control: the gap of the token the control puts first.
"""
from __future__ import annotations

import importlib
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BATCH = 2


def model_key(seed: int):
    """The model's random key from a run's seed (any non-negative integer
    below 2**64): its low and high 32 bits both count."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _ref_module(name: str):
    return importlib.import_module(f"bench.refs.{name}")


def spec_tuple(config: dict) -> tuple:
    """The configuration's scalar entries, hashable for the jitted
    reference."""
    return tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, bool, str))))


def _pack(group, seq: int, width: int):
    toks = np.zeros((BATCH, seq), np.int32)
    pos = np.zeros((BATCH, width), np.int32)
    served = np.zeros((BATCH, width), np.int32)
    valid = np.zeros((BATCH, width), bool)
    for i, (prompt, out) in enumerate(group):
        s = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        if len(s) > seq or len(out) > width:
            raise ValueError(f"sample of {len(s)} positions and {len(out)} "
                             f"tokens exceeds ({seq}, {width})")
        toks[i, :len(s)] = s
        n = len(out)
        pos[i, :n] = len(prompt) - 1 + np.arange(n)
        served[i, :n] = out
        valid[i, :n] = True
    return toks, pos, served, valid


@jax.jit
def _read(ref, served, ctl):
    best = jnp.max(ref, axis=-1)
    at = jnp.take_along_axis(ref, served[..., None], axis=-1)[..., 0]
    ctl_tok = jnp.argmax(ctl, axis=-1) if ctl is not None else served
    at_ctl = jnp.take_along_axis(ref, ctl_tok[..., None], axis=-1)[..., 0]
    return best - at, best - at_ctl


def gaps(config: dict, seed: int, samples: Sequence[Tuple[np.ndarray, list]],
         seq: int, width: int, control: bool = False) -> Tuple[List[float],
                                                              List[float]]:
    """Per served position, the reference's best logit minus its logit of
    the served token (and, with ``control``, minus its logit of the
    control's first token). ``samples`` are (prompt, served tokens)."""
    ref = _ref_module(config["reference"])
    spec, key = spec_tuple(config), model_key(seed)
    served_gaps: List[float] = []
    control_gaps: List[float] = []
    for g in range(0, len(samples), BATCH):
        group = list(samples[g:g + BATCH])
        toks, pos, served, valid = _pack(group, seq, width)
        toks, pos = jnp.asarray(toks), jnp.asarray(pos)
        logits = ref.logits_at(spec, key, toks, pos)
        ctl = ref.logits_at(spec, key, toks, pos, quant="fp8") \
            if control else None
        a, b = _read(logits, jnp.asarray(served), ctl)
        del logits, ctl
        served_gaps += np.asarray(a)[valid].tolist()
        control_gaps += np.asarray(b)[valid].tolist()
    return served_gaps, (control_gaps if control else [])
