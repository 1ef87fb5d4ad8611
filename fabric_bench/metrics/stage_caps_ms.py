"""Host milliseconds per request building the two-hop kernels' periodic
capacity table (the cases grouped by schedule content, one float32 table
and one circuit support per distinct schedule): the ``caps_ns`` counter
of the program's ``fabric.stage`` spans, part of ``stage_ms``."""

from fabric_bench import spans


def read(ctx):
    ns = spans.mean_attr(ctx, "fabric.stage", "caps_ns")
    return None if ns is None else ns * 1e-6
