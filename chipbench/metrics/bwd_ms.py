"""Device time per step of the backward pass: ops under the ``grad``
scope whose path holds ``transpose(`` and is not a recomputation."""

from chipbench import scopes


def read(ctx):
    return scopes.ms(ctx, stage="grad", direction="bwd")
