"""Device time per step of the in-step attack injection (scope
``attack``)."""

from chipbench import scopes


def read(ctx):
    return scopes.ms(ctx, stage="attack")
