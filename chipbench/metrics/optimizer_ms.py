"""Device time per step of the optimizer (scope ``optimizer``: the
schedule, the AdamW update and ``apply_updates``)."""

from chipbench import scopes


def read(ctx):
    return scopes.ms(ctx, stage="optimizer")
