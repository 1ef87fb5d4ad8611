"""Percent of the chip's idle time inside the traced ``fb.request`` spans
that no program span explains: idle time in no ``fabric.`` span, or only
in the self time of the containers ``fabric.sweep`` and ``fabric.batch``.
The spans are mapped onto the device trace's clock
(``fabric_bench.spans``)."""

from fabric_bench import spans, trace


def read(ctx):
    reqs = spans.on_trace_clock(ctx)
    if reqs is None:
        return None
    busy = ctx.trace.busy
    idle = unspanned = 0.0
    for recs, (r0, r1) in reqs:
        told = trace.union((None, a, b) for r, a, b in recs
                           if r.name not in spans.CONTAINERS)
        for g0, g1 in trace.gaps(busy, r0, r1):
            idle += g1 - g0
            unspanned += (g1 - g0) - trace.covered(told, g0, g1)
    return 100.0 * unspanned / idle if idle > 0 else None
