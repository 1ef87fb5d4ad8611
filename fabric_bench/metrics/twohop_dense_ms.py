"""Device milliseconds per request of the ``twohop_dense`` kernel: the summed
durations of its jitted module's events (``jit_twohop_dense``) in the trace."""

from fabric_bench import roofline


def read(ctx):
    return roofline.kernel_ms(ctx, "twohop_dense")
