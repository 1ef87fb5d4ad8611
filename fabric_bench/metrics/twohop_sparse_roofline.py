"""Share of the ``twohop_sparse`` kernel's roofline, in percent: the least time
of its counted work (``fabric_bench/kernels/twohop_sparse.py``, the two-hop
problem's count) at the chip's published peaks, over its device time in the
trace."""

from fabric_bench import roofline


def read(ctx):
    return roofline.share(ctx, "twohop_sparse")
