"""Device time per step of the forward recomputed in the backward pass
(``jax.checkpoint``): ops under ``grad`` whose path holds
``rematted_computation``."""

from chipbench import scopes


def read(ctx):
    return scopes.ms(ctx, stage="grad", direction="remat")
