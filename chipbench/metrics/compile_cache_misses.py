"""Persistent compilation cache misses over set-up and window, as JAX
reports them: 0 once every program of the cell is in the checkout's cache."""


def read(ctx):
    return ctx.cache_misses
