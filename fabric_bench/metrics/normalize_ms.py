"""Host milliseconds per request normalizing demand for Vermilion
construction (Algorithm 1 step 1): the program's
``fabric.construct.normalize`` spans."""

from fabric_bench import spans


def read(ctx):
    return spans.mean_self_ms(ctx, "fabric.construct.normalize")
