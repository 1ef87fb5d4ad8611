"""Simulated slots times cases per host second of serving: the sum of every
completed request's case horizons, over the host seconds the window's
requests took from the entry of each to its return."""

from fabric_bench import runner


def read(ctx):
    if not ctx.window:
        return None
    slots = sum(s.request.slots(ctx.cell) for s in ctx.window)
    return slots / runner.serving_s(ctx.window)
