"""Open-loop driver: submits requests on the wall clock and stamps tokens.

Arrivals follow the clock from the start of the pre-roll, whatever the
engine is doing: a request due while a step runs is submitted when the step
returns, and its latency still counts from when it was due. The driver
only uses the engine's public surface (``submit``, ``step``, ``busy`` and
each request's ``out``), and every ``step()`` returns only
after the sampled tokens reached the host, so a stamp taken after it is the
time the client sees the token.

Host spans (``bench.step``, ``bench.wait``, ``bench.submit``,
``bench.stamp``) go into the profiler's trace when one is recording, so
that device idle time can be put down to what the host was doing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, List, Optional, Sequence

from bench.latency import Stamps
from bench.traffic import Arrival


@dataclasses.dataclass
class StepRecord:
    """One ``step()`` call: host start and end, and the context length of
    every token a decode tick produced in it (a request's first token comes
    from its prefill and is not among them)."""
    start: float
    end: float
    decode_contexts: List[int]


@dataclasses.dataclass
class Run:
    recs: List[Stamps]
    requests: list                   # the engine's Request objects, in order
    steps: List[StepRecord]
    w0: float
    w1: float
    lateness: List[float]            # submit time - due time, per request
    traced: Optional[tuple] = None   # (start, end) host times of the trace
    in_flight_at_w0: int = 0         # requests admitted or queued then


def _null_span(_name):
    return contextlib.nullcontext()


def drive(eng, arrivals: Sequence[Arrival], preroll_s: float,
          window_s: float, *, span: Callable = _null_span,
          trace: Optional[tuple] = None) -> Run:
    """Run the schedule against ``eng`` until the window closes.

    ``trace``: (seconds, start_fn, stop_fn) records the last ``seconds`` of
    the window: ``start_fn`` is called between steps once that part begins,
    ``stop_fn`` after the window has closed."""
    clock = time.perf_counter
    t0 = clock()
    w0 = t0 + preroll_s
    w1 = w0 + window_s
    recs: List[Stamps] = []
    reqs: list = []
    live: List[int] = []
    seen: List[int] = []
    steps: List[StepRecord] = []
    lateness: List[float] = []
    traced = None
    at_w0 = None
    i, n = 0, len(arrivals)
    while True:
        now = clock()
        if now >= w1:
            break
        if at_w0 is None and now >= w0:
            at_w0 = len(live)
        if trace is not None and traced is None and now >= w1 - trace[0]:
            trace[1]()
            traced = (clock(), None)
        if i < n and t0 + arrivals[i].due_s <= now:
            with span("bench.submit"):
                while i < n and t0 + arrivals[i].due_s <= now:
                    a = arrivals[i]
                    reqs.append(eng.submit(a.prompt, max_tokens=a.max_tokens))
                    sub = clock()
                    recs.append(Stamps(t0 + a.due_s, sub, len(a.prompt)))
                    lateness.append(sub - (t0 + a.due_s))
                    live.append(len(reqs) - 1)
                    seen.append(0)
                    i += 1
        if eng.busy:
            start = clock()
            with span("bench.step"):
                eng.step()
            end = clock()
            with span("bench.stamp"):
                steps.append(StepRecord(start, end,
                                        _stamp(reqs, recs, live, seen, end)))
                live = [j for j in live if not reqs[j].done]
        else:
            nxt = t0 + arrivals[i].due_s if i < n else w1
            with span("bench.wait"):
                time.sleep(max(0.0, min(nxt, w1) - clock()))
    if traced is not None:
        traced = (traced[0], clock())
        trace[2]()
    return Run(recs, reqs, steps, w0, w1, lateness, traced, at_w0 or 0)


def _stamp(reqs, recs, live, seen, t) -> List[int]:
    """Stamp every token the last step produced with ``t``. Returns the
    decode contexts."""
    contexts = []
    for j in live:
        r, rec = reqs[j], recs[j]
        k = len(r.out)
        if k < seen[j]:                  # re-prefilled after an integrity
            rec.tokens = rec.tokens[:k]  # failure: its stream restarts
            seen[j] = k
        for m in range(seen[j], k):
            rec.tokens.append(t)
            if m > 0:
                contexts.append(rec.prompt_len + m)
        seen[j] = k
        rec.done, rec.error = r.done, r.error
    return contexts
