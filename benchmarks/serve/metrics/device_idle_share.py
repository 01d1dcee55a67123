"""Share of the traced window in which no XLA op ran on the device."""


def read(ctx):
    return (1 - ctx.trace.busy_s / ctx.trace.window_s) * 100
