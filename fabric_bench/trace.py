"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
On a TPU the trace has one plane per chip (``/device:TPU:<i>``) whose
``XLA Ops`` line holds every operation the chip ran and whose
``XLA Modules`` line holds one event per call of a jitted program, named
``jit_<function>(<id>)``; the host plane (``/host:CPU``) holds the
benchmark's own spans (``fb.request``, ``fb.construct``, ``fb.engine``).
All events share one clock, in nanoseconds.

:class:`Trace` keeps only those three kinds of interval, so the reduction
below runs the same on a trace read from disk and on a hand-made one.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "fb."


@dataclass
class Trace:
    """Intervals in seconds: device operations and jitted-module calls per
    chip, and the benchmark's host spans."""
    ops: list            # per chip: [(name, start, end)]
    modules: list        # per chip: [(name, start, end)]
    spans: list          # [(name, start, end)]


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []

    def events(line):
        return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                 * 1e-9) for e in line.events]

    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                ops.append(events(lines[OPS_LINE]))
                modules.append(events(lines[MODULES_LINE])
                               if MODULES_LINE in lines else [])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [e for e in events(ln)
                          if e[0].startswith(SPAN_PREFIX)]
    return Trace(ops, modules, sorted(spans, key=lambda s: s[1]))


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list = []
    for a, b in sorted((s, e) for _, s, e in intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def covered(merged, a: float, b: float) -> float:
    """Seconds of [a, b] that ``merged`` intervals cover."""
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in merged)


def gaps(merged, a: float, b: float) -> list:
    """The uncovered (start, end) stretches of [a, b]."""
    out, t = [], a
    for s, e in merged:
        if e <= a or s >= b:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < b:
        out.append((t, b))
    return out


@dataclass
class Reduced:
    """What the metric readers read from one traced window."""
    window: tuple        # (start, end) of the traced requests
    busy_s: float        # seconds with an operation running, mean over chips
    requests: list       # the fb.request spans
    engine: list         # the fb.engine spans
    construct: list      # the fb.construct spans
    module_s: dict       # jitted function -> device seconds, over all chips
    busy: list           # merged busy intervals of chip 0
    device_ops: list     # [[op, seconds]], the 10 longest in total
    idle_gaps: list      # [[host activity, seconds]], the 10 largest

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _op_name(event_name: str) -> str:
    """``%fusion.8 = f32[...] fusion(...)`` -> ``%fusion.8 fusion``: an
    operation's name and kind, without its HLO text."""
    head, _, rest = event_name.partition(" = ")
    kind = re.search(r"\s([a-z][\w-]*)\(", " " + rest)
    return f"{head} {kind.group(1)}" if kind else head


def _module_name(event_name: str) -> str:
    name = event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def reduce(tr: Trace) -> Reduced | None:
    """None when the trace holds no request or no device plane."""
    req = [s for s in tr.spans if s[0] == "fb.request"]
    if not req or not tr.ops:
        return None
    a, b = req[0][1], max(s[2] for s in req)
    merged = [union(chip) for chip in tr.ops]
    busy_s = sum(covered(m, a, b) for m in merged) / len(merged)
    module_s: dict = {}
    for chip in tr.modules:
        for name, s, e in chip:
            if s < b and e > a:
                k = _module_name(name)
                module_s[k] = module_s.get(k, 0.0) + min(e, b) - max(s, a)
    op_s: dict = {}
    for name, s, e in tr.ops[0]:
        if s < b and e > a:
            k = _op_name(name)
            op_s[k] = op_s.get(k, 0.0) + (e - s)
    engine = [s for s in tr.spans if s[0] == "fb.engine"]
    construct = [s for s in tr.spans if s[0] == "fb.construct"]
    return Reduced(
        window=(a, b), busy_s=busy_s, requests=req, engine=engine,
        construct=construct, module_s=module_s, busy=merged[0],
        device_ops=[[k, v] for k, v in sorted(op_s.items(),
                                               key=lambda kv: -kv[1])[:10]],
        idle_gaps=_idle_by_activity(merged[0], a, b, engine, construct))


def _idle_by_activity(busy, a, b, engine, construct) -> list:
    """Idle seconds of chip 0 in [a, b], by what the host was doing: in
    ``construct``, in ``engine`` before the engine's first device
    operation (staging), after its last (the host replay), or between
    them, and otherwise ``other``."""
    phases = [(c0, c1, "construct") for _, c0, c1 in construct]
    for _, e0, e1 in engine:
        inside = [iv for iv in busy if iv[1] > e0 and iv[0] < e1]
        if not inside:
            phases.append((e0, e1, "engine:before_device"))
            continue
        first, last = max(inside[0][0], e0), min(inside[-1][1], e1)
        phases += [(e0, first, "engine:before_device"),
                   (first, last, "engine:between_device"),
                   (last, e1, "engine:after_device")]
    cuts = sorted({t for p in phases for t in p[:2]})
    tot: dict = {}
    for s, e in gaps(busy, a, b):
        edges = [s] + [t for t in cuts if s < t < e] + [e]
        for lo, hi in zip(edges, edges[1:]):
            mid = 0.5 * (lo + hi)
            what = next((w for p0, p1, w in phases if p0 <= mid < p1),
                        "other")
            tot[what] = tot.get(what, 0.0) + (hi - lo)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            ][:10]
