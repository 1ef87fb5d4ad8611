"""Device milliseconds per request of the ``singlehop`` kernel: the summed
durations of its jitted module's events (``jit_singlehop``) in the trace."""

from fabric_bench import roofline


def read(ctx):
    return roofline.kernel_ms(ctx, "singlehop")
