"""Milliseconds per request inside the program's ``fabric.fetch`` spans
in which the chip ran nothing: copying the kernels' outputs back and the
sync, not the kernel itself.  The spans are mapped onto the device
trace's clock (``fabric_bench.spans``)."""

from fabric_bench import spans


def read(ctx):
    reqs = spans.on_trace_clock(ctx)
    if reqs is None:
        return None
    idle = sum(spans.idle_in(ctx.trace.busy, a, b)
               for recs, _ in reqs for r, a, b in recs
               if r.name == "fabric.fetch")
    return 1e3 * idle / len(reqs)
