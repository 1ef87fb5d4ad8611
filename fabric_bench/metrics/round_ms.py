"""Host milliseconds per request in Vermilion construction's integer
rounding (Algorithm 1 step 2): the program's ``fabric.construct.round``
spans."""

from fabric_bench import spans


def read(ctx):
    return spans.mean_self_ms(ctx, "fabric.construct.round")
