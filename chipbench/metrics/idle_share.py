"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices."""


def read(ctx):
    busy = ctx.trace.mean_busy_seconds()
    return 100.0 * (1.0 - busy / ctx.trace.window_s)
