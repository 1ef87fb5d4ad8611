"""Host milliseconds per request spent in the program's schedule
constructors (the benchmark's ``fb.construct`` span, on the host clock),
over the traced requests."""


def read(ctx):
    spans = [s.construct_s for s in ctx.traced]
    return 1e3 * sum(spans) / len(spans) if spans else None
