"""Device time per step of the aggregation weight solve (scope
``aggregate/solve``: FA's IRLS on the (W, W) Gram)."""

from chipbench import scopes


def read(ctx):
    return scopes.ms(ctx, stage="aggregate", sub="solve")
