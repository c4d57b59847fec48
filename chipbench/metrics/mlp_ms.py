"""Device time per step of the MLP (scope ``mlp``), forward, backward
and recomputed."""

from chipbench import scopes


def read(ctx):
    return scopes.ms(ctx, part="mlp")
