"""Device time per step of the attention mixer (scope ``attention``),
forward, backward and recomputed."""

from chipbench import scopes


def read(ctx):
    return scopes.ms(ctx, part="attention")
