"""Latency arithmetic of an open-loop run, from host-clock stamps.

Every request carries the time it was due (not the time the driver got to
submit it) and one stamp per token, taken when the ``ServeEngine.step()``
that produced it returned. Only requests due inside the window count for
the time to first token; a request that has no first token by the window's
end counts with the time it has waited, so a stall cannot hide. Gaps
between tokens and the rate take every token stamped inside the window.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class Stamps:
    due: float
    submitted: float
    prompt_len: int
    tokens: List[float] = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (numpy's linear interpolation), or None when
    there is nothing to take it of."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def due_in(recs: Sequence[Stamps], w0: float, w1: float) -> List[Stamps]:
    return [r for r in recs if w0 <= r.due < w1]


def ttfts(recs: Sequence[Stamps], w0: float, w1: float) -> List[float]:
    """Seconds from due time to first token of every request due in the
    window; censored at the window's end where no first token came."""
    out = []
    for r in due_in(recs, w0, w1):
        first = r.tokens[0] if r.tokens and r.tokens[0] <= w1 else w1
        out.append(first - r.due)
    return out


def served_in(recs: Sequence[Stamps], w0: float, w1: float) -> List[Stamps]:
    """The requests the window asked for: those due in it, and those due
    before it that were served a token in it (a backlog due at the
    pre-roll's start)."""
    return [r for r in recs if w0 <= r.due < w1
            or (r.due < w0 and any(w0 <= t <= w1 for t in r.tokens))]


def itls(recs: Sequence[Stamps], w0: float, w1: float) -> List[float]:
    """Every gap between consecutive tokens of any request whose later
    token was stamped inside the window, pooled over all requests."""
    out = []
    for r in recs:
        t = r.tokens
        out.extend(t[k] - t[k - 1] for k in range(1, len(t))
                   if w0 <= t[k] <= w1)
    return out


def tokens_in(recs: Sequence[Stamps], w0: float, w1: float) -> int:
    return sum(1 for r in recs for t in r.tokens if w0 <= t <= w1)


def rate(recs: Sequence[Stamps], w0: float, w1: float) -> float:
    """Tokens emitted in the window over the whole window's seconds."""
    return tokens_in(recs, w0, w1) / (w1 - w0)
