"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` the JAX profiler wrote into plain
lists: for each device plane its operations and its program executions,
and the harness's own host spans (``bench.*``). ``summarize`` reduces those
lists; it is plain arithmetic on intervals and is what the tests check on a
small recorded trace.

Device busy time is the union of the intervals in which an operation ran,
clipped to the traced window (the host span ``bench.traced``); the idle
share inside a host span is the part of that span's time in which no
operation ran. A program's device time is the duration of its execution
events (``jit_<name>``); a kernel's is the sum of its operation events.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"
# stats kept with each device operation event (shapes, for the rooflines)
OP_STATS = ("long_name", "tf_op")

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def extract(path: str) -> dict:
    """{"devices": [{"name", "ops": [[name, t0_ns, dur_ns, stats]],
    "modules": [[name, t0_ns, dur_ns]]}], "spans": [[name, t0_ns, dur_ns]]}
    from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        st = {k: str(v) for k, v in e.stats if k in OP_STATS}
                        dev["ops"].append([e.name, e.start_ns,
                                           e.duration_ns, st])
                elif line.name == MODULES_LINE:
                    dev["modules"] += [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def overlap(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of the part of [lo, hi] that ``merged`` covers."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``merged`` leaves uncovered."""
    out, t = [], lo
    for a, b in merged:
        if b <= lo or a >= hi:
            continue
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def program_name(module: str) -> str:
    """``jit_tick(123)`` or ``jit_tick.4`` -> ``tick``."""
    name = re.sub(r"^jit_", "", module)
    return re.split(r"[(.]", name, maxsplit=1)[0]


@dataclasses.dataclass
class Summary:
    window: Interval                      # ns, host clock of the trace
    busy: List[List[Interval]]            # per device, merged, in window
    spans: List[Tuple[str, float, float]]  # (name, start, end) ns
    programs: Dict[str, List[float]]      # name -> device seconds per run
    ops: List[Tuple[str, float, float, dict]]  # (name, start, end, stats)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        lo, hi = self.window
        per = [overlap(b, lo, hi) for b in self.busy]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def idle_share(self, span: str) -> float:
        """Share of the time inside host spans named ``span`` in which no
        operation ran on the device (averaged over the devices)."""
        sp = union([(a, b) for n, a, b in self.spans if n == span])
        total = sum(b - a for a, b in sp)
        if not total or not self.busy:
            return None
        busy = sum(sum(overlap(bz, a, b) for a, b in sp)
                   for bz in self.busy) / len(self.busy)
        return 1.0 - busy / total

    def kernel_events(self, name: str) -> List[Tuple[float, str]]:
        """(device seconds, HLO text) of every operation event of kernel
        ``name``: the instruction, or the framework op it was lowered from,
        bears the kernel's name. On the chip an event's name is the whole
        HLO instruction, whose operands may name the kernel's result too, so
        only the part before `` = `` is the instruction's own name. The text
        is the event's ``long_name`` where the trace has one, else its
        name."""
        return [((b - a) * 1e-9, st.get("long_name") or n)
                for n, a, b, st in self.ops
                if name in n.split(" = ", 1)[0]
                or name in st.get("tf_op", "")]

    def breakdown(self, n: int = 10) -> dict:
        """The device operations that took most time, and the longest idle
        gaps, each cut where the host moved from one span to the next and
        named by the span it fell in."""
        tot: Dict[str, float] = {}
        for name, a, b, _ in self.ops:
            tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        lo, hi = self.window
        spans = sorted((a, b, name) for name, a, b in self.spans
                       if name != WINDOW_SPAN)
        named = []
        for a, b in (gaps(self.busy[0], lo, hi) if self.busy else []):
            cuts = sorted({a, b} | {t for s, e, _ in spans for t in (s, e)
                                    if a < t < b})
            for x, y in zip(cuts, cuts[1:]):
                mid = (x + y) / 2
                owner = next((nm for s, e, nm in spans if s <= mid < e),
                             "outside_spans")
                named.append([owner, (y - x) * 1e-9])
        named.sort(key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": named[:n]}


def summarize(ex: dict) -> Summary:
    spans = [(n, float(t), float(t + d)) for n, t, d in ex["spans"]]
    win = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if win:
        window = (min(a for a, _ in win), max(b for _, b in win))
    else:
        ts = [t for _, a, b in spans for t in (a, b)]
        window = (min(ts), max(ts))
    lo, hi = window
    busy, programs, ops = [], {}, []
    for dev in ex["devices"]:
        iv = [(float(t), float(t + d)) for _, t, d, _ in dev["ops"]
              if t + d > lo and t < hi]
        busy.append(union(iv))
        for name, t, d in dev["modules"]:
            if t >= lo and t + d <= hi:
                programs.setdefault(program_name(name), []).append(d * 1e-9)
        ops += [(name, float(t), float(t + d), st)
                for name, t, d, st in dev["ops"] if t >= lo and t + d <= hi]
    return Summary(window, busy, spans, programs, ops)
