"""The serving program's own instrumentation in a profiler trace, and a
tool that records it for one cell.

    python3 bench/program_trace.py --workload <cell> --seed <n> \\
        --seconds <s> --out <dir>

The program names its device work with ``jax.named_scope`` (``kv_view``
with ``kv_gather``, ``kv_mac``, ``kv_unseal`` and ``kv_mask`` inside it,
``kv_append``, ``kv_copy``, ``weight_decrypt``, ``attention``,
``sampling``; the kernel ``sealed_matmul`` keeps its own name) and leaves
host spans on the profiler's clock: ``serve.step`` around ``serve.admit``,
``serve.chunk``, ``serve.decode`` (each dispatch with a ``.readback``
child), ``serve.integrity`` and ``serve.evict``. ``serve.decode`` carries
the paged view's block counters as arguments.

``extract`` reads all of it from an ``.xplane.pb``, in the form
``bench/trace.py`` reads (each device operation's stats gain its scope
path, ``op_name``), and adds the program's spans with their arguments
under ``program_spans``; ``reduce`` turns it into:

- per program (``tick``, ``chunk_step``): the device time of each scope
  per execution, summed over the leaf operations that ran inside the
  program's executions (``while``, ``conditional`` and ``call`` events
  enclose their bodies' operations, and are not counted again);
- the counters' shares over the window's ``serve.decode`` spans;
- the host's work per step: a ``serve.step`` span less its readbacks;
- the device's idle time, put down to the innermost span that holds it.

The tool runs a cell as ``bench/run.py --trace 1`` does (the same set-up,
open-loop schedule and traced part of the window), keeps the trace under
``--out`` and prints one JSON line: that reduction, the benchmark's own
per-layer metrics read off the same trace, and the 95th percentile of
the gaps between tokens in the traced part and in the rest of the window.
"""
from __future__ import annotations

import argparse
import bisect
import json
import re
import sys
import types
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.path.insert(1, str(ROOT / "src"))

from bench import trace as TR  # noqa: E402

PROGRAM_PREFIX = "serve."
# the program's scopes: the paged view and its parts, then the rest
SCOPES = ("kv_view", "kv_gather", "kv_mac", "kv_unseal", "kv_mask",
          "kv_append", "kv_copy", "weight_decrypt", "attention", "sampling")
# scopes that do not nest in one another: each leaf operation falls in one
# of them, in the kernel, or in none (``other``)
TOP = ("kv_view", "kv_append", "kv_copy", "weight_decrypt", "attention",
       "sampling")
KERNEL = "sealed_matmul"
CONTAINERS = ("while", "conditional", "call")
COUNTERS = ("blocks_gathered", "blocks_resident", "blocks_reserved",
            "running")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def extract(path: str) -> dict:
    """``bench/trace.py``'s extract of one ``.xplane.pb`` with every stat
    of each device operation kept, and the program's host spans with
    their arguments: ``program_spans`` [[name, t0_ns, dur_ns, args]]."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans, program = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == TR.OPS_LINE:
                    dev["ops"] += [[e.name, e.start_ns, e.duration_ns,
                                    {k: str(v) for k, v in e.stats}]
                                   for e in line.events]
                elif line.name == TR.MODULES_LINE:
                    dev["modules"] += [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(TR.SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append([e.name, e.start_ns, e.duration_ns,
                                        {k: v for k, v in e.stats}])
    return {"devices": devices, "spans": spans, "program_spans": program}


def instruction(name: str) -> str:
    """The HLO instruction's own name in an operation event's name (on
    the chip the name is the whole instruction)."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def is_container(name: str) -> bool:
    """A ``while``, ``conditional`` or ``call``: its event encloses the
    events of its bodies' operations."""
    head = instruction(name)
    return re.sub(r"[.\d]+$", "", head) in CONTAINERS or any(
        f" {c}(" in name for c in CONTAINERS)


def hlo_op_names(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of every instruction in an HLO
    module's text that carries one."""
    out = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=", line)
        if m:
            op = OP_NAME.search(line)
            if op:
                out[m.group(1)] = op.group(1)
    return out


def op_path(name: str, stats: dict, program: Optional[str] = None,
            hlo: Optional[Dict[str, Dict[str, str]]] = None) -> str:
    """An operation's scope path: its ``tf_op`` stat, else the
    ``op_name`` its HLO text carries, else the one the program's HLO
    (``hlo``: program -> instruction -> op_name) gives its instruction."""
    if stats.get("tf_op"):
        return stats["tf_op"]
    for text in (stats.get("long_name", ""), name):
        m = OP_NAME.search(text)
        if m:
            return m.group(1)
    if hlo and program in hlo:
        return hlo[program].get(instruction(name), "")
    return ""


def _top(name: str, parts: List[str]) -> str:
    if KERNEL in instruction(name):
        return KERNEL
    return next((p for p in parts if p in TOP), "other")


def scope_times(ex: dict, window, hlo=None,
                n_other: int = 12) -> Dict[str, dict]:
    """Per program: its executions inside the window (``runs``), their
    mean device time (``device_ms``), the mean sum of the leaf operations
    inside them (``leaf_ms``), each scope's share of that sum per
    execution (``scopes_ms``; a scope holds its inner scopes' time), and
    the same sum split between the scopes that do not nest, the kernel and
    the rest (``split_ms``, which adds up to ``leaf_ms``), and the
    operations of the rest that took most time (``other_top``: [the
    event's name, cut to 120 characters, its ``op_name``, ms])."""
    lo, hi = window
    acc: Dict[str, dict] = {}
    for dev in ex["devices"]:
        runs = sorted((float(t), float(t + d), TR.program_name(n))
                      for n, t, d in dev["modules"]
                      if t >= lo and t + d <= hi)
        starts = [a for a, _, _ in runs]
        for a, b, prog in runs:
            p = acc.setdefault(prog, {"runs": 0, "device": 0.0, "leaf": 0.0,
                                      "scopes": {}, "split": {},
                                      "other": {}})
            p["runs"] += 1
            p["device"] += b - a
        for name, t, d, st in dev["ops"]:
            if is_container(name):
                continue
            i = bisect.bisect_right(starts, float(t)) - 1
            if i < 0 or t + d > runs[i][1]:
                continue
            prog = runs[i][2]
            p = acc[prog]
            path = op_path(name, st, prog, hlo)
            parts = path.split("/")
            p["leaf"] += d
            for s in set(parts) & set(SCOPES):
                p["scopes"][s] = p["scopes"].get(s, 0.0) + d
            top = _top(name, parts)
            p["split"][top] = p["split"].get(top, 0.0) + d
            if top == "other":
                key = (name[:120], path)
                p["other"][key] = p["other"].get(key, 0.0) + d
    ms = lambda v, n: v / n * 1e-6
    return {prog: {"runs": p["runs"],
                   "device_ms": ms(p["device"], p["runs"]),
                   "leaf_ms": ms(p["leaf"], p["runs"]),
                   "scopes_ms": {s: ms(v, p["runs"])
                                 for s, v in sorted(p["scopes"].items())},
                   "split_ms": {s: ms(v, p["runs"])
                                for s, v in sorted(p["split"].items())},
                   "other_top": [[nm, path, ms(v, p["runs"])]
                                 for (nm, path), v in sorted(
                                     p["other"].items(),
                                     key=lambda kv: -kv[1])[:n_other]]}
            for prog, p in acc.items()}


def _in_window(spans, window, name=None):
    lo, hi = window
    return [s for s in spans if s[1] >= lo and s[1] + s[2] <= hi
            and (name is None or s[0] == name)]


def counters(ex: dict, window) -> dict:
    """The ``serve.decode`` counters summed over the window, and their
    shares: blocks resident over blocks the view gathered
    (``kv_view_useful_share``) and over blocks reserved
    (``kv_pool_resident_share``), in %."""
    dec = _in_window(ex["program_spans"], window, "serve.decode")
    tot = {k: sum(int(s[3].get(k, 0)) for s in dec) for k in COUNTERS}
    out = {"ticks": len(dec), **tot}
    if tot["blocks_gathered"]:
        out["kv_view_useful_share"] = (
            100.0 * tot["blocks_resident"] / tot["blocks_gathered"])
    if tot["blocks_reserved"]:
        out["kv_pool_resident_share"] = (
            100.0 * tot["blocks_resident"] / tot["blocks_reserved"])
    return out


def host_sched_ms(ex: dict, window) -> Optional[float]:
    """Mean over the window's ``serve.step`` spans of the time in which
    the host worked between the device's results: the span less the time
    its ``*.readback`` spans cover."""
    spans = ex["program_spans"]
    steps = _in_window(spans, window, "serve.step")
    reads = TR.union([(t, t + d) for n, t, d, _ in spans
                      if n.endswith(".readback")])
    if not steps:
        return None
    work = [d - TR.overlap(reads, t, t + d) for _, t, d, _ in steps]
    return sum(work) / len(work) * 1e-6


def idle_by_span(ex: dict, summary: TR.Summary, n: int = 10) -> dict:
    """The device's idle gaps in the window, cut where the host entered or
    left a span and put down to the innermost span (the harness's and the
    program's) that holds each piece: the longest pieces, and the idle
    seconds per span name."""
    lo, hi = summary.window
    # (start, end, rank, name): of two spans over the same time the
    # program's (rank 0) is the inner one, as eng.step() runs inside
    # the harness's span
    spans = [(float(t), float(t + d), 1, nm) for nm, t, d in ex["spans"]
             if nm != TR.WINDOW_SPAN]
    spans += [(float(t), float(t + d), 0, nm)
              for nm, t, d, _ in ex["program_spans"]]
    # the owner of each segment between consecutive span boundaries
    bounds = sorted({t for s, e, _, _ in spans for t in (s, e)})
    owners = []
    for x, y in zip(bounds, bounds[1:]):
        mid = (x + y) / 2
        inner = [(e - s, -s, r, nm) for s, e, r, nm in spans
                 if s <= mid < e]
        owners.append(min(inner)[3] if inner else "outside_spans")
    pieces, per = [], {}
    for a, b in (TR.gaps(summary.busy[0], lo, hi) if summary.busy else []):
        j = bisect.bisect_right(bounds, a) - 1
        x = a
        while x < b:
            y = min(b, bounds[j + 1]) if j + 1 < len(bounds) else b
            owner = owners[j] if 0 <= j < len(owners) else "outside_spans"
            pieces.append([owner, (y - x) * 1e-9])
            per[owner] = per.get(owner, 0.0) + (y - x) * 1e-9
            x, j = y, j + 1
    pieces.sort(key=lambda kv: -kv[1])
    return {"idle_gaps": pieces[:n],
            "idle_s_by_span": dict(sorted(per.items(),
                                          key=lambda kv: -kv[1]))}


def span_times(ex: dict, window) -> Dict[str, list]:
    """Per program span name: [count, mean ms] in the window."""
    out: Dict[str, list] = {}
    for nm, _, d, _ in _in_window(ex["program_spans"], window):
        c = out.setdefault(nm, [0, 0.0])
        c[0] += 1
        c[1] += d * 1e-6
    return {nm: [c, tot / c] for nm, (c, tot) in sorted(out.items())}


def reduce(ex: dict, hlo=None) -> dict:
    """Everything above, over the window ``bench/trace.py`` takes."""
    s = TR.summarize(ex)
    return {"window_s": s.window_s, "busy_s": s.busy_s,
            "programs": scope_times(ex, s.window, hlo),
            "counters": counters(ex, s.window),
            "host_sched_ms": host_sched_ms(ex, s.window),
            "spans_ms": span_times(ex, s.window),
            **idle_by_span(ex, s)}


def main(argv=None) -> int:
    import jax

    from bench import driver, latency, roofline, run, spec
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True, help="directory for the trace")
    args = ap.parse_args(argv)
    cell = spec.load(ROOT, args.workload)
    dev = run.open_chip(cell)
    eng, arrivals = run.prepare(cell, args.seed, args.seconds)
    tdir = str(Path(args.out) / "trace")
    ann = jax.profiler.TraceAnnotation(TR.WINDOW_SPAN)

    def start():
        jax.profiler.start_trace(tdir)
        ann.__enter__()

    def stop():
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    r = driver.drive(eng, arrivals, float(cell.traffic["preroll_s"]),
                     args.seconds, span=jax.profiler.TraceAnnotation,
                     trace=(run.TRACE_SECONDS, start, stop))
    ex = extract(TR.find_xplane(tdir))
    out = reduce(ex, {prog: hlo_op_names(text)
                      for prog, text in eng.compiled_hlo().items()})
    # the benchmark's own per-layer metrics, read off the same trace
    ctx = types.SimpleNamespace(run=r, trace=TR.summarize(ex),
                                config=cell.config,
                                peak=roofline.peaks(dev["kind"]))
    out["per_layer"] = {k: v["value"]
                        for k, v in run.per_layer(cell, ctx).items()}
    t = r.traced[0]
    p95 = lambda a, b: latency.percentile(latency.itls(r.recs, a, b), 95)
    out["itl_p95_ms"] = {k: (v * 1e3 if v is not None else None) for k, v in
                         (("window", p95(r.w0, r.w1)),
                          ("untraced_part", p95(r.w0, t)),
                          ("traced_part", p95(t, r.w1)))}
    out["stats"] = {k: v for k, v in eng.stats.items()
                    if isinstance(v, (int, float))}
    print(json.dumps({"workload": cell.name, "seed": args.seed, **out}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
