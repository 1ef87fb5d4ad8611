"""Seconds from the start of the process to the start of the window:
starting JAX, generating the pool's traffic, and serving each pool
request once, which compiles (or loads from the cache) every shape the
window uses."""


def read(ctx):
    return ctx.setup_s
