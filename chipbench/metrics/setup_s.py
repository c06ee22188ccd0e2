"""Seconds from process start to the first timed call: imports, data,
autotune lookup, compilation-cache loads and the warm-up call."""


def read(ctx):
    return ctx.setup_s
