"""From the program's own spans to the numbers the span-based per-layer
metrics read.

The program records a span at each boundary of its served path
(``repro.core.tracing``: ``fabric.sweep`` > ``fabric.batch`` >
``fabric.stage`` / ``fabric.dispatch`` / ``fabric.fetch`` /
``fabric.replay`` / ``fabric.results``, and ``fabric.construct`` with
its ``normalize`` / ``round`` / ``decompose`` stages), on the host's
``perf_counter`` clock, into a bounded ring it keeps in memory.  A traced
request's records are those whose interval lies inside its
``[Served.t0, Served.t1]``; each value below is a mean per traced request.

A layer's *self time* is its span's duration minus the part of that
interval its child spans cover.  The two readers that need the chip map
the records onto the device trace's clock per request: the offset is the
request's ``fb.request`` span start in the trace minus its ``Served.t0``.

A program without the recorder (an older checkout) has no records: every
reader then returns None.
"""
from __future__ import annotations

from . import trace

# spans that only group others: their self time explains no idle time
CONTAINERS = ("fabric.sweep", "fabric.batch")


def per_request(ctx) -> list | None:
    """The program's records inside each traced request, or None when the
    program records none."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    if not ctx.traced:
        return None
    recs = tracing.records()
    out = []
    for s in ctx.traced:
        a, b = s.t0 * 1e9, s.t1 * 1e9
        out.append([r for r in recs if r.t0_ns >= a and r.t1_ns <= b])
    return out if any(out) else None


def self_ns(rec, recs) -> float:
    """``rec``'s duration less the part of it its children cover."""
    kids = trace.union((None, r.t0_ns, r.t1_ns) for r in recs
                       if r.parent_id == rec.id)
    return (rec.t1_ns - rec.t0_ns) - trace.covered(kids, rec.t0_ns,
                                                   rec.t1_ns)


def mean_self_ms(ctx, name: str) -> float | None:
    """Mean per traced request of the summed self time of ``name``, in
    ms; None where no such span ran."""
    reqs = per_request(ctx)
    if reqs is None:
        return None
    vals = [self_ns(r, recs) for recs in reqs for r in recs
            if r.name == name]
    return sum(vals) / len(reqs) * 1e-6 if vals else None


def mean_attr(ctx, name: str, key: str) -> float | None:
    """Mean per traced request of counter ``key`` summed over ``name``
    spans; None where no such span counted it."""
    reqs = per_request(ctx)
    if reqs is None:
        return None
    vals = [r.attrs[key] for recs in reqs for r in recs
            if r.name == name and key in r.attrs]
    return sum(vals) / len(reqs) if vals else None


def on_trace_clock(ctx) -> list | None:
    """Per traced request: its records as ``(record, start, end)`` in
    trace seconds, and its ``fb.request`` interval; None without a device
    trace, or where the trace's requests do not pair with the served
    ones."""
    reqs = per_request(ctx)
    t = ctx.trace
    if reqs is None or t is None or len(t.requests) != len(ctx.traced):
        return None
    out = []
    for recs, s, (_, r0, r1) in zip(reqs, ctx.traced, t.requests):
        off = r0 - s.t0
        out.append(([(r, r.t0_ns * 1e-9 + off, r.t1_ns * 1e-9 + off)
                     for r in recs], (r0, r1)))
    return out


def idle_in(busy, a: float, b: float) -> float:
    """Seconds of [a, b] in which the chip ran nothing."""
    return (b - a) - trace.covered(busy, a, b)
