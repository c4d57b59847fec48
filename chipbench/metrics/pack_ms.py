"""Device time per step of the Gram pack copy (scope
``aggregate/pack``: the leaves concatenated into one (W, N) stack)."""

from chipbench import scopes


def read(ctx):
    return scopes.ms(ctx, stage="aggregate", sub="pack")
