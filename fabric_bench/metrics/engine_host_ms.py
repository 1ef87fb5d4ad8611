"""Host milliseconds per request inside the served entry point
(``run_sweep``) during which the chip ran nothing: the benchmark's
``fb.engine`` span minus the device-busy time inside it, both from the
trace.  Staging, transfers and the host flow replay land here."""

from fabric_bench import trace


def read(ctx):
    t = ctx.trace
    if t is None or not t.engine:
        return None
    host = sum((e - s) - trace.covered(t.busy, s, e) for _, s, e in t.engine)
    return 1e3 * host / len(t.engine)
