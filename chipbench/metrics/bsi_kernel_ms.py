"""Device milliseconds per registered pair in Pallas kernels (today these
are only the BSI forward and adjoint), from the traced window."""


def read(ctx):
    seconds = sum(ctx.trace.kernel_s.values()) if ctx.trace else 0.0
    return 1e3 * seconds / ctx.pairs if seconds > 0 else None
