"""Share of the ``singlehop`` kernel's roofline, in percent: the least time
of its counted work (``fabric_bench/kernels/singlehop.py``) at the chip's
published peaks, over its device time in the trace."""

from fabric_bench import roofline


def read(ctx):
    return roofline.share(ctx, "singlehop")
