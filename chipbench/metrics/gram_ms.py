"""Device time of the Pallas Gram kernel (op ``tree_gram``) per step and
device."""

PATTERN = r"\btree_gram\b"


def read(ctx):
    return ctx.per_step_ms(PATTERN)
