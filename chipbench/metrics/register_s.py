"""Seconds per registered pair, one pair per call: the window's elapsed
time to the end of its last whole call over the pairs registered."""


def read(ctx):
    if ctx.cell.traffic["batch"] != 1:
        return None
    return ctx.window_s / ctx.pairs
