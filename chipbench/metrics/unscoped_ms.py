"""Device time per step of the step's ops under no stage scope (copies
and the like that XLA adds)."""

from chipbench import scopes


def read(ctx):
    return scopes.ms(ctx, stage=None)
