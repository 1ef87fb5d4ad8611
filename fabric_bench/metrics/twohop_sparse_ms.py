"""Device milliseconds per request of the ``twohop_sparse`` kernel: the summed
durations of its jitted module's events (``jit_twohop_sparse``) in the trace."""

from fabric_bench import roofline


def read(ctx):
    return roofline.kernel_ms(ctx, "twohop_sparse")
