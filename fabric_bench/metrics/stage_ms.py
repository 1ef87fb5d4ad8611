"""Host milliseconds per request building the kernels' inputs: the
self time of the program's ``fabric.stage`` spans (plan tiling, arrival
lists, the two-hop capacity table and plan LUT)."""

from fabric_bench import spans


def read(ctx):
    return spans.mean_self_ms(ctx, "fabric.stage")
