"""Percent of the traced window in which the chip ran no operation: one
minus the union of the device's operation intervals over the window."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
