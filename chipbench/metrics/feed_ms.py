"""Device time per step of every module in the traced window other than
the train step: the jitted feed and the small programs that place each
step's key and index."""

from chipbench import scopes


def read(ctx):
    return scopes.other_module_seconds(ctx.trace, ctx.step_module) \
        / ctx.steps * 1e3
