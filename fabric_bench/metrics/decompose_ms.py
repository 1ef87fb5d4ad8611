"""Host milliseconds per request decomposing Vermilion's emulated
multigraphs into perfect matchings: the program's
``fabric.construct.decompose`` spans."""

from fabric_bench import spans


def read(ctx):
    return spans.mean_self_ms(ctx, "fabric.construct.decompose")
