"""Host milliseconds per request in the exact flow-credit replay after
the kernel: the self time of the program's ``fabric.replay`` spans (no
device wait: the outputs were fetched before it)."""

from fabric_bench import spans


def read(ctx):
    return spans.mean_self_ms(ctx, "fabric.replay")
