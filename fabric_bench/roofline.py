"""A kernel's share of its roofline over a traced window: the least time
its counted work could take on this chip, over the device time its
jitted module took."""
from __future__ import annotations

import importlib

from . import harness, peaks


def kernel_ms(ctx, kernel: str) -> float | None:
    """Device milliseconds of ``kernel``'s module per traced request."""
    t = ctx.trace
    if t is None or not t.module_s.get(kernel):
        return None
    return t.module_s[kernel] / len(ctx.traced) * 1e3


def share(ctx, kernel: str) -> float | None:
    """Percent of the roofline; None where the kernel did not run."""
    t = ctx.trace
    if t is None or not t.module_s.get(kernel):
        return None
    counts = importlib.import_module(f"fabric_bench.kernels.{kernel}")
    flops = nbytes = 0.0
    for s in ctx.traced:
        f, b = counts.count(
            harness.kernel_batches(ctx.cell, s.request)[counts.BATCH])
        flops, nbytes = flops + f, nbytes + b
    least, _ = peaks.least_seconds(flops, nbytes, ctx.device_kind)
    return 100.0 * least / t.module_s[kernel]
