"""One generator for every traffic mix: a mix is a data file of parameters.

A mix file (``bench/traffic/<mix>.json``) gives the arrival process and
its rate, the prompt and output length distributions and the pre-roll.
Every seed draws the same multiset of lengths and inter-arrival gaps
(stratified quantiles of the distributions) in another order, with its own
token ids: two seeds load the system with the same work, so their runs
differ by arrangement and not by amount. The order is drawn in blocks of
``BLOCK`` requests, each of which holds one value from each of ``BLOCK``
strata of the sorted values: a window that serves only the schedule's first
requests, as an overloaded one does, then gets the same work from every
seed too.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

BLOCK = 8


@dataclasses.dataclass
class Arrival:
    """One request of the schedule: due ``due_s`` after the pre-roll
    starts, with its prompt ids and the number of tokens it asks for."""
    due_s: float
    prompt: np.ndarray
    max_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the stratified quantiles of ``dist``, clipped to
    [min, max], in ascending order."""
    q = _quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        x = lo + q * (hi - lo + 1)
        x = np.floor(x)
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(v)) for v in q])
        x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def request_count(mix: dict, rate_rps: float, window_s: float) -> int:
    """How many requests the schedule holds, in whole blocks: an open-loop
    mix spans its pre-roll and the window at ``rate_rps``; a backlog is its
    size."""
    if mix["arrival"] == "backlog":
        n = int(mix["backlog"])
    elif mix["arrival"] == "poisson":
        n = math.ceil(rate_rps * (mix["preroll_s"] + window_s))
    else:
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    return BLOCK * max(1, math.ceil(n / BLOCK))


def blocked(values: np.ndarray, rng) -> np.ndarray:
    """``values`` (ascending, whole blocks) in an order drawn from ``rng``
    in which every block of ``BLOCK`` consecutive entries takes one value
    from each stratum, the s-th ``1 / BLOCK`` of the sorted values."""
    strata = np.asarray(values).reshape(BLOCK, -1)
    picked = np.stack([s[rng.permutation(s.size)] for s in strata], axis=1)
    return np.concatenate([b[rng.permutation(BLOCK)] for b in picked])


def schedule(mix: dict, rate_rps: float, window_s: float, seed: int,
             vocab: int) -> List[Arrival]:
    """The requests of one run, sorted by due time (seconds from the start
    of the pre-roll). A backlog is all due at the pre-roll's start, so that
    the window sees every slot busy."""
    n = request_count(mix, rate_rps, window_s)
    rng = np.random.default_rng(seed)
    plen = blocked(lengths(mix["prompt"], n), rng)
    olen = blocked(lengths(mix["output"], n), rng)
    if mix["arrival"] == "poisson":
        gaps = -np.log1p(-_quantiles(n)) / rate_rps     # exponential quantiles
        due = np.cumsum(blocked(gaps, rng))
    else:
        due = np.zeros(n)
    return [Arrival(float(due[i]),
                    rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                    int(olen[i]))
            for i in range(n)]
