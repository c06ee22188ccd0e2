"""Pairs registered per second: all pairs of the window's whole calls over
the elapsed time to the end of the last call."""


def read(ctx):
    return ctx.pairs / ctx.window_s
