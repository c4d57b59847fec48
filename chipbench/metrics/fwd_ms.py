"""Device time per step of the forward pass: ops under the ``grad``
scope that are neither backward (``transpose(``) nor recomputed."""

from chipbench import scopes


def read(ctx):
    return scopes.ms(ctx, stage="grad", direction="fwd")
