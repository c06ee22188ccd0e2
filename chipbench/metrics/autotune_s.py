"""Host seconds in the autotuner's ``resolve_options`` during set-up: the
race of BSI forms on a cold checkout, a cache lookup after it."""


def read(ctx):
    return ctx.autotune_s
