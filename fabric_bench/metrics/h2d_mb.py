"""Megabytes (1e6 bytes) per request handed to the device kernels: the
``h2d_bytes`` counter of the program's ``fabric.stage`` spans, the summed
size of every array a kernel call takes."""

from fabric_bench import spans


def read(ctx):
    b = spans.mean_attr(ctx, "fabric.stage", "h2d_bytes")
    return None if b is None else b * 1e-6
