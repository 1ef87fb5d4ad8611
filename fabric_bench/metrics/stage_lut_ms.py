"""Host milliseconds per request building the ``twohop_sparse`` kernel's
circuit-support lookup table (``_sparse_plan_lut``): the ``lut_ns``
counter of the program's ``fabric.stage`` spans, part of ``stage_ms``."""

from fabric_bench import spans


def read(ctx):
    ns = spans.mean_attr(ctx, "fabric.stage", "lut_ns")
    return None if ns is None else ns * 1e-6
