"""Device time of the train step's XLA module per step, averaged over
the cell's devices."""


def read(ctx):
    t = ctx.trace
    secs = [t.module_seconds(ctx.step_module, d) for d in t.devices]
    total = sum(secs) / len(secs)
    return total / ctx.steps * 1e3 if total > 0 else None
